"""Mamba2 SSD (state-space duality) block: chunked scan + one-step decode.

Follows the minimal SSD formulation of Dao & Gu (arXiv:2405.21060): within a
chunk the output is a masked "attention" (C B^T ∘ L) X; across chunks a small
recurrence carries the (heads, head_dim, state) SSM state.  The Pallas TPU
kernel in ``repro.kernels.ssd_scan`` implements the chunk kernel; this module
is the pure-jnp reference used on CPU and as the kernel oracle.

Sharding note: the fused in_proj of the reference CUDA implementation is
split into per-component projections (z/x/B/C/dt) so the big d_inner pieces
can be TP-sharded over "model" without slicing a sharded dimension at
non-aligned offsets; the depthwise conv is likewise split (a depthwise conv
over a concatenation == separate depthwise convs).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.sharding import hint
from repro.models.common import rms_norm, spec


def ssm_spec(cfg: ModelConfig):
    d, di, n, hh = cfg.d_model, cfg.d_inner, cfg.ssm_state, cfg.ssm_heads
    w = cfg.conv_width
    sc = d ** -0.5
    return {
        "in_z": spec((d, di), ("embed", "ff"), sc),
        "in_x": spec((d, di), ("embed", "ff"), sc),
        "in_B": spec((d, n), ("embed", "state"), sc),
        "in_C": spec((d, n), ("embed", "state"), sc),
        "in_dt": spec((d, hh), ("embed", "heads"), sc),
        "conv_x": spec((w, di), ("conv", "ff"), 0.2),
        "conv_x_b": spec((di,), ("ff",), 0.0),
        "conv_B": spec((w, n), ("conv", "state"), 0.2),
        "conv_B_b": spec((n,), ("state",), 0.0),
        "conv_C": spec((w, n), ("conv", "state"), 0.2),
        "conv_C_b": spec((n,), ("state",), 0.0),
        "a_log": spec((hh,), ("heads",), 1.0),   # A = -exp(a_log) ~ -e
        "d_skip": spec((hh,), ("heads",), 1.0),
        "dt_bias": spec((hh,), ("heads",), 0.0),
        "norm": spec((di,), ("ff",), 1.0),
        "out_proj": spec((di, d), ("ff", "embed"),
                         di ** -0.5 / (2 * max(cfg.num_layers, 1)) ** 0.5),
    }


def _segsum(a):
    """(..., l) -> (..., l, l) lower-triangular segment sums (excl. diag of a_j)."""
    l = a.shape[-1]
    cs = jnp.cumsum(a, axis=-1)
    ss = cs[..., :, None] - cs[..., None, :]
    mask = jnp.tril(jnp.ones((l, l), bool))
    return jnp.where(mask, ss, -jnp.inf)


def ssd_scan(x, dt, a_log, B, C, chunk: int, init_state=None):
    """Chunked SSD.

    x (b,s,h,p); dt (b,s,h) >=0 (post-softplus); a_log (h,), A = -exp(a_log);
    B,C (b,s,n).  Returns y (b,s,h,p) fp32 and final state (b,h,p,n) fp32.

    Precision policy (§Perf iteration M2): decay math (cumsum/exp/segsum) and
    state accumulation stay fp32; the big (b,s,...) tensors carried between
    einsums keep the INPUT dtype (bf16 in training), with fp32 matmul
    accumulation via preferred_element_type.  Halves the HBM traffic of the
    jnp path; fp32 inputs (tests/oracles) are bit-identical to before.
    """
    b, s, h, p = x.shape
    n = B.shape[-1]
    if s % chunk != 0:
        chunk = s
    nc = s // chunk
    cdt = x.dtype                                           # compute dtype
    A = -jnp.exp(a_log.astype(jnp.float32))                 # (h,)
    da = dt.astype(jnp.float32) * A                         # (b,s,h) log-decays
    xb = (x.astype(jnp.float32)
          * dt.astype(jnp.float32)[..., None]).astype(cdt)

    def r(t, trailing):
        return t.reshape((b, nc, chunk) + trailing)

    xc, dac = r(xb, (h, p)), r(da, (h,))
    Bc, Cc = r(B.astype(cdt), (n,)), r(C.astype(cdt), (n,))
    cum = jnp.cumsum(dac, axis=2)                           # (b,nc,l,h) inclusive

    # 1) intra-chunk: y_diag[l] = sum_{m<=l} (C_l.B_m) L[l,m] x_m
    L = jnp.exp(_segsum(dac.transpose(0, 1, 3, 2)))         # (b,nc,h,l,m) fp32
    scores = jnp.einsum("bcln,bcmn->bclm", Cc, Bc,
                        preferred_element_type=jnp.float32)
    y_diag = jnp.einsum("bchlm,bcmhp->bclhp",
                        (scores[:, :, None] * L).astype(cdt), xc,
                        preferred_element_type=jnp.float32)

    # 2) chunk-final states: S_c = sum_m exp(sum_{j>m} da_j) B_m x_m^T
    dec_end = jnp.exp(cum[:, :, -1:, :] - cum).astype(cdt)  # (b,nc,l,h)
    states = jnp.einsum("bclh,bcln,bclhp->bchpn", dec_end, Bc, xc,
                        preferred_element_type=jnp.float32)

    # 3) inter-chunk recurrence (fp32: small (b,h,p,n) state)
    chunk_decay = jnp.exp(cum[:, :, -1, :])                 # (b,nc,h)
    s0 = (jnp.zeros((b, h, p, n), jnp.float32) if init_state is None
          else init_state.astype(jnp.float32))

    def step(carry, inp):
        st, dec = inp
        return carry * dec[:, :, None, None] + st, carry    # emit entering state

    final, prev = jax.lax.scan(
        step, s0, (states.transpose(1, 0, 2, 3, 4), chunk_decay.transpose(1, 0, 2)))
    prev = prev.transpose(1, 0, 2, 3, 4)                    # (b,nc,h,p,n)

    # 4) carry-in contribution: y_off[l] = C_l . (exp(cum[l]) S_prev)
    y_off = jnp.einsum("bcln,bchpn,bclh->bclhp", Cc.astype(jnp.float32),
                       prev, jnp.exp(cum))

    y = y_diag + y_off
    return y.reshape(b, s, h, p), final


def _conv1d_causal(x, w, b, cache=None):
    """Depthwise causal conv. x (b,s,c); w (wd,c); cache (b,wd-1,c) or None."""
    wd = w.shape[0]
    pad = (jnp.zeros((x.shape[0], wd - 1, x.shape[2]), x.dtype)
           if cache is None else cache.astype(x.dtype))
    xp = jnp.concatenate([pad, x], axis=1)
    new_cache = xp[:, x.shape[1]:, :]  # last wd-1 inputs
    out = sum(xp[:, i: i + x.shape[1], :] * w[i][None, None, :] for i in range(wd))
    return out + b[None, None, :], new_cache


def init_ssm_cache(cfg: ModelConfig, batch: int, dtype):
    """Per-layer decode cache leaves (stacked by the model over layers)."""
    w = cfg.conv_width
    return {
        "conv_x": jnp.zeros((batch, w - 1, cfg.d_inner), dtype),
        "conv_B": jnp.zeros((batch, w - 1, cfg.ssm_state), dtype),
        "conv_C": jnp.zeros((batch, w - 1, cfg.ssm_state), dtype),
        "state": jnp.zeros((batch, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state),
                           jnp.float32),
    }


def ssm_cache_axes():
    return {
        "conv_x": ("batch", "conv", "ff"),
        "conv_B": ("batch", "conv", "state"),
        "conv_C": ("batch", "conv", "state"),
        "state": ("batch", "heads", None, "state"),
    }


def mamba2_block(xin, p, cfg: ModelConfig, cache=None, single_step: bool = False):
    """Mamba2 mixer. xin (b,s,d) -> out (b,s,d) [, new_cache if cache given]."""
    b, s, d = xin.shape
    di, n, hh, hp = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads, cfg.ssm_head_dim
    z = xin @ p["in_z"]
    xs = xin @ p["in_x"]
    Braw = xin @ p["in_B"]
    Craw = xin @ p["in_C"]
    dt_raw = xin @ p["in_dt"]
    cc = cache or {}
    xs, ncx = _conv1d_causal(xs, p["conv_x"], p["conv_x_b"], cc.get("conv_x"))
    B, ncB = _conv1d_causal(Braw, p["conv_B"], p["conv_B_b"], cc.get("conv_B"))
    C, ncC = _conv1d_causal(Craw, p["conv_C"], p["conv_C_b"], cc.get("conv_C"))
    xs, B, C = jax.nn.silu(xs), jax.nn.silu(B), jax.nn.silu(C)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32)
                         + p["dt_bias"].astype(jnp.float32))
    xh = xs.reshape(b, s, hh, hp)
    xh = hint(xh, "batch", None, "heads", None)

    if single_step:
        A = -jnp.exp(p["a_log"].astype(jnp.float32))
        dec = jnp.exp(dt[:, 0, :] * A)                      # (b,h)
        st = (cache["state"].astype(jnp.float32) * dec[:, :, None, None]
              + jnp.einsum("bh,bn,bhp->bhpn", dt[:, 0],
                           B[:, 0].astype(jnp.float32),
                           xh[:, 0].astype(jnp.float32)))
        y = jnp.einsum("bn,bhpn->bhp", C[:, 0].astype(jnp.float32), st)[:, None]
        new_state = st
    else:
        with jax.named_scope("ssd_scan"):
            y, new_state = ssd_scan(xh, dt, p["a_log"], B, C, cfg.ssm_chunk,
                                    init_state=cc.get("state"))
    y = y + (xh.astype(jnp.float32)
             * p["d_skip"].astype(jnp.float32)[None, None, :, None])
    y = y.reshape(b, s, di).astype(xin.dtype)
    y = rms_norm(y * jax.nn.silu(z), p["norm"], cfg.norm_eps)
    out = y @ p["out_proj"]
    if cache:
        return out, {"conv_x": ncx, "conv_B": ncB, "conv_C": ncC,
                     "state": new_state}
    return out
