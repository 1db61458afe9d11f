"""Dense decoder with grouped-query attention (Llama layout: RMSNorm, RoPE,
SwiGLU), as the SmolLM model card and the Llama paper (arXiv:2302.13971)
describe it, in float32.

Departure kept from the program: the output head is its own matrix (untied
from the embedding).  Each block is ``x += attn(norm1(x)); x += mlp(norm2(x))``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.reference.common import F32, Numerics, nll_sum, rms_norm, silu


def dims(c: dict) -> dict:
    """Sizes the reference needs, from the configuration file's keys."""
    h = c["num_attention_heads"]
    return {"L": c["num_hidden_layers"], "d": c["hidden_size"], "h": h,
            "m": c["num_key_value_heads"],
            "k": c.get("head_dim") or c["hidden_size"] // h,
            "f": c["intermediate_size"], "V": c["vocab_size"],
            "theta": float(c["rope_theta"]), "eps": float(c["rms_norm_eps"])}


def layout(c: dict) -> dict:
    """(shape, init scale) of every weight, in the pytree the program holds.
    Per-layer weights are stacked along a leading layer axis."""
    z = dims(c)
    L, d, h, m, k, f, V = (z[n] for n in "LdhmkfV")
    out_scale = (2 * L) ** -0.5
    return {
        "embed": ((V, d), d ** -0.5),
        "final_norm": ((d,), 1.0),
        "unembed": ((d, V), d ** -0.5),
        "blocks": {
            "ln1": ((L, d), 1.0),
            "ln2": ((L, d), 1.0),
            "attn": {"wq": ((L, d, h, k), d ** -0.5),
                     "wk": ((L, d, m, k), d ** -0.5),
                     "wv": ((L, d, m, k), d ** -0.5),
                     "wo": ((L, h, k, d), (h * k) ** -0.5 * out_scale)},
            "mlp": {"w_gate": ((L, d, f), d ** -0.5),
                    "w_up": ((L, d, f), d ** -0.5),
                    "w_down": ((L, f, d), f ** -0.5 * out_scale)},
        },
    }


def rope(x, positions, theta):
    """Rotary embedding, rotate-half convention. x (b, s, heads, k)."""
    k = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, k, 2, dtype=jnp.float32) / k)
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, axis=-1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, axis=-1)[None, :, None, :]
    x1, x2 = x[..., : k // 2], x[..., k // 2:]
    return x * cos + jnp.concatenate([-x2, x1], axis=-1) * sin


def _block(x, p, z, num: Numerics):
    b, s, _ = x.shape
    pos = jnp.arange(s)
    hn = rms_norm(x, p["ln1"], z["eps"])
    q = rope(num.mm("bsd,dhk->bshk", hn, p["attn"]["wq"]), pos, z["theta"])
    kk = rope(num.mm("bsd,dmk->bsmk", hn, p["attn"]["wk"]), pos, z["theta"])
    vv = num.mm("bsd,dmk->bsmk", hn, p["attn"]["wv"])
    rep = z["h"] // z["m"]                       # query head i reads kv head i // rep
    kk = jnp.repeat(kk, rep, axis=2)
    vv = jnp.repeat(vv, rep, axis=2)
    scores = num.mm("bshk,bthk->bhst", q, kk) / jnp.sqrt(jnp.float32(z["k"]))
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    o = num.mm("bhst,bthk->bshk", probs, vv)
    x = x + num.mm("bshk,hkd->bsd", o, p["attn"]["wo"])
    hn = rms_norm(x, p["ln2"], z["eps"])
    gate = silu(num.mm("bsd,df->bsf", hn, p["mlp"]["w_gate"]))
    up = num.mm("bsd,df->bsf", hn, p["mlp"]["w_up"])
    return x + num.mm("bsf,fd->bsd", gate * up, p["mlp"]["w_down"])


def logits(params, tokens, c: dict, num: Numerics = F32):
    """(b, s) int tokens -> (b, s, V) float32 logits, layer by layer with
    each layer's activations recomputed on the way back."""
    z = dims(c)
    x = params["embed"][tokens].astype(jnp.float32)
    body = jax.checkpoint(lambda x, p: (_block(x, p, z, num), None))
    x, _ = jax.lax.scan(body, x, params["blocks"])
    x = rms_norm(x, params["final_norm"], z["eps"])
    return num.mm("bsd,dv->bsv", x, params["unembed"])


def loss_sum(params, tokens, labels, c: dict, num: Numerics = F32):
    """Sum of the next-token negative log-likelihoods of a block of rows."""
    return nll_sum(logits(params, tokens, c, num), labels)


# ------------------------------------------------------------------ counts --

def matmul_params(c: dict) -> int:
    """Weights that enter a matrix product per token (the embedding lookup
    is a gather and is not counted; the output head is)."""
    z = dims(c)
    L, d, h, m, k, f, V = (z[n] for n in "LdhmkfV")
    per_layer = d * h * k + 2 * d * m * k + h * k * d + 3 * d * f
    return L * per_layer + d * V


def train_flops_per_seq(c: dict, s: int) -> float:
    """Model FLOPs of one training sequence of s tokens: 6 per matmul weight
    and token, plus causal attention (QK^T and PV over the s(s+1)/2 pairs a
    causal mask needs, forward and twice that backward).  Recomputation is
    not counted."""
    z = dims(c)
    attn_fwd = 2 * z["L"] * z["h"] * z["k"] * s * (s + 1)
    return 6.0 * matmul_params(c) * s + 3.0 * attn_fwd


def decode_cost(c: dict, batch: int, pos: int, *, weight_bytes: int,
                cache_bytes: int) -> tuple[float, float]:
    """(FLOPs, HBM bytes) one decode step needs at 0-based position ``pos``
    for ``batch`` rows: every weight read once, the key/value cache read up
    to and including ``pos`` and written at ``pos``, the logits written in
    float32.  Attention FLOPs cover the ``pos + 1`` positions attended."""
    z = dims(c)
    L, d, h, m, k, V = (z[n] for n in "LdhmkV")
    n_w = matmul_params(c) + L * 2 * d + d            # + norm scales
    flops = 2.0 * matmul_params(c) * batch + 4.0 * L * batch * h * k * (pos + 1)
    kv_row = 2 * L * m * k * cache_bytes              # keys and values, all layers
    nbytes = (n_w * weight_bytes + batch * d * weight_bytes     # + embedding rows
              + batch * (pos + 1) * kv_row + batch * kv_row     # read + write
              + batch * V * 4)
    return flops, float(nbytes)


def program_fields(c: dict) -> dict:
    """The configuration as the program's ModelConfig names it."""
    z = dims(c)
    return {"num_layers": z["L"], "d_model": z["d"], "num_heads": z["h"],
            "num_kv_heads": z["m"], "head_dim": z["k"], "d_ff": z["f"],
            "vocab_size": z["V"], "rope_theta": z["theta"], "norm_eps": z["eps"],
            "act": "swiglu"}
