"""Mamba2 language model (SSD mixer, arXiv:2405.21060), in float32.

Each block is ``x += mixer(norm(x))``.  The mixer projects the input to a
gate z, the inputs x, B and C (one group) and a step size per head; x, B and
C pass a depthwise causal convolution and SiLU; dt = softplus(dt_raw +
dt_bias); with A = -exp(a_log) per head the state space runs

    y_t = sum_{u<=t} (C_t . B_u) exp(sum_{u<j<=t} dt_j A) dt_u x_u + D x_t,

which this reference evaluates in its quadratic (attention-like) form over
the whole sequence, not chunk by chunk.  Then y is normed after the gate,
``norm(y * silu(z))``, and projected out.

Departures kept from the program: the projections are separate matrices (a
split of the published fused in_proj), the output head is untied, and the
residual stream is not kept in float32 apart from the rest.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.reference.common import F32, Numerics, nll_sum, rms_norm, silu


def dims(c: dict) -> dict:
    ssm = c["ssm_cfg"]
    d = c["d_model"]
    di = ssm["expand"] * d
    mult = c["pad_vocab_size_multiple"]
    return {"L": c["n_layer"], "d": d, "di": di, "N": ssm["d_state"],
            "P": ssm["headdim"], "H": di // ssm["headdim"], "w": ssm["d_conv"],
            "chunk": ssm["chunk_size"],
            "V": -(-c["vocab_size"] // mult) * mult, "eps": 1e-5}


def layout(c: dict) -> dict:
    z = dims(c)
    L, d, di, N, H, w, V = (z[n] for n in ("L", "d", "di", "N", "H", "w", "V"))
    sc = d ** -0.5
    return {
        "embed": ((V, d), d ** -0.5),
        "final_norm": ((d,), 1.0),
        "unembed": ((d, V), d ** -0.5),
        "blocks": {
            "ln": ((L, d), 1.0),
            "mixer": {
                "in_z": ((L, d, di), sc), "in_x": ((L, d, di), sc),
                "in_B": ((L, d, N), sc), "in_C": ((L, d, N), sc),
                "in_dt": ((L, d, H), sc),
                "conv_x": ((L, w, di), 0.2), "conv_x_b": ((L, di), 0.0),
                "conv_B": ((L, w, N), 0.2), "conv_B_b": ((L, N), 0.0),
                "conv_C": ((L, w, N), 0.2), "conv_C_b": ((L, N), 0.0),
                "a_log": ((L, H), 1.0), "d_skip": ((L, H), 1.0),
                "dt_bias": ((L, H), 0.0), "norm": ((L, di), 1.0),
                "out_proj": ((L, di, d), di ** -0.5 * (2 * L) ** -0.5),
            },
        },
    }


def _conv(x, w, b):
    """Depthwise causal convolution over the sequence: x (b, s, c), w (k, c);
    out_t = sum_i w_i x_{t-k+1+i} + bias."""
    k, s = w.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    return sum(xp[:, i:i + s] * w[i] for i in range(k)) + b


def _mixer(h, p, z, num: Numerics):
    b, s, _ = h.shape
    H, P = z["H"], z["P"]
    zg = num.mm("bsd,de->bse", h, p["in_z"])
    x = silu(_conv(num.mm("bsd,de->bse", h, p["in_x"]), p["conv_x"], p["conv_x_b"]))
    B = silu(_conv(num.mm("bsd,dn->bsn", h, p["in_B"]), p["conv_B"], p["conv_B_b"]))
    C = silu(_conv(num.mm("bsd,dn->bsn", h, p["in_C"]), p["conv_C"], p["conv_C_b"]))
    dt = jax.nn.softplus(num.mm("bsd,dh->bsh", h, p["in_dt"]) + p["dt_bias"])
    A = -jnp.exp(p["a_log"])                                   # (H,)
    cs = jnp.cumsum(dt * A, axis=1)                            # (b, s, H)
    seg = cs[:, :, None, :] - cs[:, None, :, :]                # (b, t, u, H)
    causal = jnp.tril(jnp.ones((s, s), bool))[None, :, :, None]
    decay = jnp.where(causal, jnp.exp(jnp.where(causal, seg, 0.0)), 0.0)
    cb = num.mm("btn,bun->btu", C, B)                          # (b, t, u)
    xh = x.reshape(b, s, H, P)
    weights = cb[..., None] * decay * dt[:, None, :, :]        # (b, t, u, H)
    y = num.mm("btuh,buhp->bthp", weights, xh) + p["d_skip"][:, None] * xh
    y = rms_norm(y.reshape(b, s, -1) * silu(zg), p["norm"], z["eps"])
    return num.mm("bse,ed->bsd", y, p["out_proj"])


def logits(params, tokens, c: dict, num: Numerics = F32):
    z = dims(c)
    x = params["embed"][tokens].astype(jnp.float32)

    def body(x, p):
        return x + _mixer(rms_norm(x, p["ln"], z["eps"]), p["mixer"], z, num), None

    x, _ = jax.lax.scan(jax.checkpoint(body), x, params["blocks"])
    x = rms_norm(x, params["final_norm"], z["eps"])
    return num.mm("bsd,dv->bsv", x, params["unembed"])


def loss_sum(params, tokens, labels, c: dict, num: Numerics = F32):
    return nll_sum(logits(params, tokens, c, num), labels)


# ------------------------------------------------------------------ counts --

def matmul_params(c: dict) -> int:
    """Weights that enter a matrix product per token: the five input
    projections, the output projection and the output head (the embedding
    lookup, the convolutions and the per-head scalars are not counted)."""
    z = dims(c)
    L, d, di, N, H, V = (z[n] for n in ("L", "d", "di", "N", "H", "V"))
    return L * (2 * d * di + 2 * d * N + d * H + di * d) + d * V


def ssd_fwd_flops_per_seq(c: dict, s: int) -> float:
    """FLOPs of the SSD state space over one sequence of s tokens in its
    chunked form with chunk length l: inside each chunk C.B^T and the
    weighted sum over the l(l+1)/2 causal pairs, then the chunk states
    (sum B x^T) and their read-out by C for every token."""
    z = dims(c)
    l = min(z["chunk"], s)
    nc = s // l
    intra = nc * l * (l + 1) * (z["N"] + z["H"] * z["P"])
    states = 4.0 * s * z["H"] * z["P"] * z["N"]
    return z["L"] * (intra + states)


def train_flops_per_seq(c: dict, s: int) -> float:
    """6 per matmul weight and token, plus the SSD FLOPs forward and twice
    that backward.  Recomputation is not counted."""
    return 6.0 * matmul_params(c) * s + 3.0 * ssd_fwd_flops_per_seq(c, s)


def program_fields(c: dict) -> dict:
    """The configuration as the program's ModelConfig names it."""
    z = dims(c)
    return {"num_layers": z["L"], "d_model": z["d"], "vocab_size": z["V"],
            "ssm_state": z["N"], "ssm_head_dim": z["P"],
            "ssm_expand": z["di"] // z["d"], "ssm_chunk": z["chunk"],
            "conv_width": z["w"], "norm_eps": z["eps"]}
