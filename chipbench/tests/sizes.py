"""Reduced sizes of each configuration and mix, for the CPU tests."""
import copy

from chipbench import bench, generator

TINY_DENSE = {"hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 2,
              "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
              "vocab_size": 256}
TINY_MAMBA = {"d_model": 64, "n_layer": 2, "vocab_size": 250,
              "ssm_cfg": {"layer": "Mamba2", "d_state": 16, "d_conv": 4, "expand": 2,
                          "headdim": 16, "ngroups": 1, "chunk_size": 16}}


def tiny_config(name: str) -> dict:
    c = copy.deepcopy(bench.config(name))
    c.update(TINY_DENSE if c["family"] == "dense_gqa" else TINY_MAMBA)
    return c


def tiny_mix(name: str) -> dict:
    m = copy.deepcopy(generator.load(name))
    if m["kind"] == "train":
        m.update(global_batch=4, seq_len=32, pool=4, reference_rows=2)
    else:
        m.update(batch=2, max_seq=48, requests=[[8, 6], [16, 12], [5, 3]])
    return m
