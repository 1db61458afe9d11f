"""Operation and byte counts against hand counts at two sizes."""
import pytest

from chipbench.reference import dense_gqa, mamba2

DENSE = {"num_hidden_layers": 2, "hidden_size": 8, "num_attention_heads": 2,
         "num_key_value_heads": 1, "head_dim": 4, "intermediate_size": 16,
         "vocab_size": 32, "rope_theta": 10000.0, "rms_norm_eps": 1e-5}
MAMBA = {"n_layer": 2, "d_model": 8, "vocab_size": 30, "pad_vocab_size_multiple": 8,
         "ssm_cfg": {"d_state": 4, "d_conv": 4, "expand": 2, "headdim": 4,
                     "chunk_size": 4}}


def test_dense_counts_by_hand():
    # per layer: q 8*2*4 + k,v 2*8*1*4 + o 2*4*8 + mlp 3*8*16 = 64+64+64+384
    assert dense_gqa.matmul_params(DENSE) == 2 * 576 + 8 * 32
    # s = 3: 6*N*s + 3 * (2 * L * h * k * s * (s+1)) = 6*1408*3 + 3*2*2*2*4*3*4
    assert dense_gqa.train_flops_per_seq(DENSE, 3) == 6 * 1408 * 3 + 3 * 384
    f, b = dense_gqa.decode_cost(DENSE, 2, 4, weight_bytes=2, cache_bytes=2)
    assert f == 2 * 1408 * 2 + 4 * 2 * 2 * 2 * 4 * 5
    # weights (1408 + norms 2*2*8 + 8) + 2 embedding rows, cache 2 rows x 5
    # positions x (k,v 2 layers 1 head 4) read, one row written, f32 logits
    kv_row = 2 * 2 * 1 * 4 * 2
    assert b == (1408 + 40) * 2 + 2 * 8 * 2 + 2 * 5 * kv_row + 2 * kv_row + 2 * 32 * 4


@pytest.mark.parametrize("s,chunk", [(8, 4), (6, 8)])
def test_mamba_counts_by_hand(s, chunk):
    cfg = dict(MAMBA, ssm_cfg=dict(MAMBA["ssm_cfg"], chunk_size=chunk))
    # per layer: in_z, in_x 8*16 each, in_B, in_C 8*4, in_dt 8*4, out 16*8; head 8*32
    n = 2 * (2 * 128 + 2 * 32 + 32 + 128) + 8 * 32
    assert mamba2.matmul_params(cfg) == n
    l = min(chunk, s)
    intra = (s // l) * l * (l + 1) * (4 + 4 * 4)
    states = 4 * s * 4 * 4 * 4
    assert mamba2.ssd_fwd_flops_per_seq(cfg, s) == 2 * (intra + states)
    assert mamba2.train_flops_per_seq(cfg, s) == 6 * n * s + 3 * 2 * (intra + states)
