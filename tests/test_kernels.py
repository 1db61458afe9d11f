"""Pallas kernels vs. pure-jnp oracles: shape/dtype sweeps (interpret=True)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.decode_attention.ops import decode_attention
from repro.kernels.decode_attention.ref import decode_attention_ref
from repro.kernels.quant8.ops import dequantize8, int8_roundtrip, quantize8
from repro.kernels.quant8.ref import quantize8_ref
from repro.kernels.topk_ef.ops import topk_ef
from repro.kernels.ssd_scan.kernel import ssd_scan_kernel
from repro.kernels.ssd_scan.ops import ssd_scan_fused
from repro.kernels.ssd_scan.ref import ssd_scan_ref
from repro.models.ssm import ssd_scan as ssd_jnp

RNG = np.random.default_rng(7)


def _arr(shape, dtype=jnp.float32, scale=1.0):
    return jnp.asarray(RNG.standard_normal(shape) * scale, dtype)


# ------------------------------------------------------------ decode ---------

@pytest.mark.parametrize("b,h,m,d,S,length,dtype", [
    (2, 8, 2, 64, 2048, 1500, jnp.float32),
    (1, 4, 4, 128, 1024, 1024, jnp.float32),
    (3, 6, 2, 32, 512, 100, jnp.float32),
    (2, 4, 1, 64, 768, 700, jnp.bfloat16),
])
def test_decode_attention(b, h, m, d, S, length, dtype):
    q = _arr((b, h, d), dtype)
    k = _arr((b, S, m, d), dtype)
    v = _arr((b, S, m, d), dtype)
    o = decode_attention(q, k, v, length, block_k=256, interpret=True)
    g = h // m
    qf = q.reshape(b, m, g, d).reshape(b * m, g, d)
    kf = k.transpose(0, 2, 1, 3).reshape(b * m, S, d)
    vf = v.transpose(0, 2, 1, 3).reshape(b * m, S, d)
    ref = decode_attention_ref(qf, kf, vf, length, sm_scale=d ** -0.5)
    ref = ref.reshape(b, m, g, d).reshape(b, h, d)
    tol = 3e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(ref, np.float32), atol=tol, rtol=tol)


# ------------------------------------------------------------ ssd ------------

@pytest.mark.parametrize("bh,s,p,n,chunk", [
    (4, 256, 64, 32, 64), (2, 128, 32, 16, 32), (3, 96, 16, 8, 32),
    (1, 64, 128, 64, 16),
])
def test_ssd_kernel_vs_recurrence(bh, s, p, n, chunk):
    x = _arr((bh, s, p))
    dt = jnp.abs(_arr((bh, s), scale=0.2))
    a = -jnp.abs(_arr((bh,))) - 0.5
    B, C = _arr((bh, s, n)), _arr((bh, s, n))
    y, st = ssd_scan_kernel(x, dt, a, B, C, chunk=chunk, interpret=True)
    yr, sr = ssd_scan_ref(x, dt, a, B, C)
    np.testing.assert_allclose(y, yr, atol=1e-3, rtol=1e-3)
    np.testing.assert_allclose(st, sr, atol=1e-3, rtol=1e-3)


def test_ssd_fused_matches_model_path():
    b, s, h, p, n = 2, 128, 4, 32, 16
    x = _arr((b, s, h, p))
    dt = jnp.abs(_arr((b, s, h), scale=0.2))
    a_log = _arr((h,), scale=0.3)
    B, C = _arr((b, s, n)), _arr((b, s, n))
    yk, stk = ssd_scan_fused(x, dt, a_log, B, C, chunk=32, interpret=True)
    yj, stj = ssd_jnp(x, dt, a_log, B, C, 32)
    np.testing.assert_allclose(yk, yj, atol=1e-3, rtol=1e-3)
    np.testing.assert_allclose(stk, stj, atol=1e-3, rtol=1e-3)


# ------------------------------------------------------------ quant8 ---------

@pytest.mark.parametrize("shape", [(1000,), (33, 70), (4, 256), (7, 13, 11)])
def test_quant8_roundtrip(shape):
    x = _arr(shape, scale=3.0)
    q, s = quantize8(x, interpret=True)
    flat = x.reshape(-1)
    pad = (-flat.shape[0]) % 256
    xf = jnp.concatenate([flat, jnp.zeros((pad,))]).reshape(-1, 256)
    qr, _ = quantize8_ref(xf)
    assert jnp.array_equal(q, qr)
    xd = dequantize8(q, s, shape, interpret=True)
    # blockwise max-abs scaling: error bounded by scale/2 per element
    assert float(jnp.max(jnp.abs(xd - x))) <= float(jnp.max(s)) * 0.51


def test_quant_block_is_the_codec_wire_constant():
    """codecs.QUANT_BLOCK (plain int, no jax import) must equal the
    kernel's BLOCK -- int8_wire_floats meters what the silicon ships."""
    from repro.core.comm.codecs import QUANT_BLOCK
    from repro.kernels.quant8.kernel import BLOCK
    assert QUANT_BLOCK == BLOCK


@pytest.mark.parametrize("shape", [(256,), (1000,), (33, 70), (7, 13, 11),
                                   (300 * 256 + 17,)])
def test_quant8_ef_kernel_vs_ref_bitwise(shape):
    """The fused EF kernel and the straight-line oracle agree bit-for-bit
    through the same padded-tile plumbing (both fuse identically under
    jit -- see quant8/ref.py on FMA contraction)."""
    x = _arr(shape, scale=3.0)
    qk, sk, dk, ek = int8_roundtrip(x, interpret=True, backend="kernel")
    qr, sr, dr, er = int8_roundtrip(x, backend="ref")
    assert jnp.array_equal(qk, qr)
    assert jnp.array_equal(sk, sr)
    assert jnp.array_equal(dk, dr)
    assert jnp.array_equal(ek, er)
    assert qk.shape == (-(-x.size // 256), 256) and sk.shape == (qk.shape[0], 1)
    # residual == x - deq to the last ulp; deq/err keep the input's shape
    assert dk.shape == ek.shape == x.shape
    np.testing.assert_allclose(np.asarray(ek), np.asarray(x - dk), atol=1e-6)


# ------------------------------------------------------------ topk_ef --------

@pytest.mark.parametrize("shape,k", [
    ((1000,), 50), ((33, 70), 100), ((4, 256), 1), ((512,), 512),
    ((7, 13, 11), 13),
])
def test_topk_ef_kernel_vs_ref(shape, k):
    """Kernel vs oracle parity incl. k=1 and k=n edges; kept + residual
    reconstructs x bitwise (disjoint supports, no float error)."""
    x = _arr(shape, scale=2.0)
    ok, rk = topk_ef(x, k, interpret=True, backend="kernel")
    orf, rrf = topk_ef(x, k, backend="ref")
    assert jnp.array_equal(ok, orf)
    assert jnp.array_equal(rk, rrf)
    assert jnp.array_equal(ok + rk, x)
    assert not bool(jnp.any((ok != 0) & (rk != 0)))
    # gaussian draws have no magnitude ties: exactly k survive
    assert int(jnp.count_nonzero(ok)) == k


def test_topk_ef_residual_carry_three_rounds():
    """EF loop: each round's kept + residual equals its input bitwise, and
    the filtered mass is deferred, not lost -- with no new gradient the
    carried residual drains to zero in ceil(n/k) further rounds."""
    rng = np.random.default_rng(3)
    g = jnp.asarray(rng.standard_normal(640), jnp.float32)
    res = jnp.zeros_like(g)
    for _ in range(3):
        x = g + res
        out, res = topk_ef(x, 64, interpret=True)
        assert jnp.array_equal(out + res, x)
        assert not bool(jnp.any((out != 0) & (res != 0)))
    for _ in range(10):
        _, res = topk_ef(res, 64, interpret=True)
    assert float(jnp.max(jnp.abs(res))) == 0.0


# ------------------------------------------------------------ calibration ----

def test_measured_mfu_snapshot_consistency():
    """The committed BENCH_kernels.json measurement, the in-code fallback
    constant, and the resolve knob all agree."""
    from repro.core.calibration import MEASURED_MFU, measured_mfu, resolve_mfu
    m = measured_mfu()
    assert 0.0 < m <= 1.0
    assert abs(m - MEASURED_MFU) < 0.005
    assert resolve_mfu("measured") == m
    assert resolve_mfu(0.4) == 0.4
    with pytest.raises(ValueError):
        resolve_mfu("vibes")
