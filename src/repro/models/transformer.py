"""Unified model builder for all 10 assigned architectures.

Families: dense | moe (grok / deepseek-MLA) | encoder (hubert) | vlm
(llama-3.2-vision) | ssm (mamba2) | hybrid (zamba2).

All families share: scan-over-layers with stacked params (small HLO, fast
compile for the 512-device dry-run), RMSNorm, RoPE, fp32 logits, and a
decode path against an explicit cache pytree.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.sharding import current as sharding_ctx, hint
from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.common import (
    abstract_params, cross_entropy, init_params, param_axes, rms_norm, spec,
    stack_spec,
)

AUX_COEF = 0.01  # load-balance loss weight


# ================================================================ specs ======

def mlp_spec(cfg: ModelConfig, d_ff: int | None = None):
    d, f = cfg.d_model, d_ff or cfg.d_ff
    out_scale = f ** -0.5 / (2 * cfg.num_layers) ** 0.5
    s = {"w_up": spec((d, f), ("embed", "ff"), d ** -0.5),
         "w_down": spec((f, d), ("ff", "embed"), out_scale)}
    if cfg.act == "swiglu":
        s["w_gate"] = spec((d, f), ("embed", "ff"), d ** -0.5)
    return s


def _attn_spec(cfg: ModelConfig):
    return attn.mla_spec(cfg) if cfg.use_mla else attn.gqa_spec(cfg)


def _block_spec(cfg: ModelConfig, kind: str):
    ln = lambda: spec((cfg.d_model,), ("embed",), 1.0)  # noqa: E731
    if kind == "attn_mlp":
        return {"ln1": ln(), "attn": _attn_spec(cfg), "ln2": ln(),
                "mlp": mlp_spec(cfg)}
    if kind == "attn_moe":
        return {"ln1": ln(), "attn": _attn_spec(cfg), "ln2": ln(),
                "moe": moe_mod.moe_spec(cfg)}
    if kind == "attn_dense_first":  # deepseek layer 0
        return {"ln1": ln(), "attn": _attn_spec(cfg), "ln2": ln(),
                "mlp": mlp_spec(cfg, cfg.dense_d_ff)}
    if kind == "cross":
        return {"ln1": ln(), "attn": attn.gqa_spec(cfg), "ln2": ln(),
                "mlp": mlp_spec(cfg)}
    if kind == "ssm":
        return {"ln": ln(), "mixer": ssm_mod.ssm_spec(cfg)}
    raise ValueError(kind)


def model_spec(cfg: ModelConfig):
    d, v = cfg.d_model, cfg.vocab_size
    s: dict[str, Any] = {}
    if not cfg.is_encoder:
        s["embed"] = spec((v, d), ("vocab", "embed"), 1.0 / (d ** 0.5))
    s["final_norm"] = spec((d,), ("embed",), 1.0)
    s["unembed"] = spec((d, v), ("embed", "vocab"), d ** -0.5)

    fam = cfg.family
    if fam in ("dense", "encoder"):
        s["blocks"] = stack_spec(_block_spec(cfg, "attn_mlp"), cfg.num_layers)
    elif fam == "moe":
        n_moe = cfg.num_layers - cfg.first_k_dense
        if cfg.first_k_dense:
            s["first"] = stack_spec(_block_spec(cfg, "attn_dense_first"),
                                    cfg.first_k_dense)
        s["blocks"] = stack_spec(_block_spec(cfg, "attn_moe"), n_moe)
    elif fam == "vlm":
        k = cfg.cross_attn_every
        assert cfg.num_layers % k == 0
        g = cfg.num_layers // k
        s["blocks"] = stack_spec({
            "self": stack_spec(_block_spec(cfg, "attn_mlp"), k - 1, "inner"),
            "cross": _block_spec(cfg, "cross"),
        }, g)
    elif fam == "ssm":
        s["blocks"] = stack_spec(_block_spec(cfg, "ssm"), cfg.num_layers)
    elif fam == "hybrid":
        k = cfg.attn_every
        assert cfg.num_layers % k == 0
        g = cfg.num_layers // k
        s["blocks"] = stack_spec(
            {"ssm": stack_spec(_block_spec(cfg, "ssm"), k, "inner")}, g)
        s["shared_attn"] = _block_spec(cfg, "attn_mlp")  # ONE copy, reused
    else:
        raise ValueError(fam)
    return s


# ============================================================ forward ========

def mlp_apply(x, p, cfg: ModelConfig):
    with jax.named_scope("mlp"):
        if cfg.act == "swiglu":
            h = jax.nn.silu(x @ p["w_gate"]) * (x @ p["w_up"])
        else:
            h = jax.nn.gelu(x @ p["w_up"])
        h = hint(h, "batch", None, "ff")
        # output hinted seq-sharded so the TP partial-sum lowers to
        # reduce-scatter (Megatron-SP) instead of all-reduce + slice (§Perf L3)
        return hint(h @ p["w_down"], "batch", "seq", "embed")


def _self_attn(x, p, cfg, *, causal, positions):
    with jax.named_scope("attn"):
        if cfg.use_mla:
            return attn.mla_attention(x, p, cfg, causal=causal, positions=positions)
        return attn.gqa_attention(x, p, cfg, causal=causal, positions=positions)


def _attn_block(x, p, cfg, *, causal, positions, ff_fn):
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    x = x + _self_attn(h, p["attn"], cfg, causal=causal, positions=positions)
    h = rms_norm(x, p["ln2"], cfg.norm_eps)
    x = x + ff_fn(h)
    return hint(x, "batch", "seq", "embed")


def _cross_block(x, p, cfg, *, img):
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    x = x + attn.gqa_attention(h, p["attn"], cfg, causal=False, positions=None,
                               kv_src=img, use_rope=False)
    h = rms_norm(x, p["ln2"], cfg.norm_eps)
    x = x + mlp_apply(h, p["mlp"], cfg)
    return x


def _ssm_block(x, p, cfg):
    return x + ssm_mod.mamba2_block(rms_norm(x, p["ln"], cfg.norm_eps),
                                    p["mixer"], cfg)


def _wrap_remat(fn, remat: str):
    if remat == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    if remat == "full":
        return jax.checkpoint(fn)
    return fn


def scan_blocks(body, carry, xs, scan: bool = True):
    """lax.scan or an unrolled Python loop (same contract).

    Unrolling lets XLA overlap per-layer collectives across layers (a §Perf
    lever) at the cost of compile time; scan keeps the 512-device dry-run
    HLO small.
    """
    if scan:
        return jax.lax.scan(body, carry, xs)
    n = jax.tree.leaves(xs)[0].shape[0]
    ys = []
    for i in range(n):
        xi = jax.tree.map(lambda a: a[i], xs)
        carry, y = body(carry, xi)
        ys.append(y)
    if ys and ys[0] is not None:
        ys = jax.tree.map(lambda *a: jnp.stack(a), *ys)
    else:
        ys = None
    return carry, ys


def _moe_groups() -> int:
    ctx = sharding_ctx()
    if ctx is None:
        return 1
    axes = ctx.map.get("batch") or ()
    g = 1
    for a in (axes if isinstance(axes, tuple) else (axes,)):
        g *= ctx.mesh.shape[a]
    return max(g, 1)


def forward(params, batch, cfg: ModelConfig, *, remat: str = "none",
            last_only: bool = False, scan_layers: bool = True):
    """-> (logits (b,s,v) fp32, aux scalar). last_only: unembed final position
    only (prefill lowering: avoids a (b,s,vocab) logits buffer)."""
    fam = cfg.family
    causal = not cfg.is_encoder
    if cfg.is_encoder:
        x = batch["frames"].astype(jnp.dtype(cfg.dtype))
    else:
        x = jnp.take(params["embed"], batch["tokens"], axis=0)
    x = hint(x, "batch", "seq", "embed")
    s = x.shape[1]
    positions = jnp.arange(s)
    aux0 = jnp.zeros((), jnp.float32)

    if fam in ("dense", "encoder"):
        def body(carry, bp):
            return _attn_block(carry, bp, cfg, causal=causal, positions=positions,
                               ff_fn=lambda h: mlp_apply(h, bp["mlp"], cfg)), None
        x, _ = scan_blocks(_wrap_remat(body, remat), x, params["blocks"], scan_layers)
        aux = aux0

    elif fam == "moe":
        groups = _moe_groups()
        if cfg.first_k_dense:
            def fbody(carry, bp):
                return _attn_block(carry, bp, cfg, causal=True,
                                   positions=positions,
                                   ff_fn=lambda h: mlp_apply(h, bp["mlp"], cfg)), None
            x, _ = scan_blocks(_wrap_remat(fbody, remat), x, params["first"], scan_layers)

        def body(carry, bp):
            x, aux = carry
            h = rms_norm(x, bp["ln1"], cfg.norm_eps)
            x = x + _self_attn(h, bp["attn"], cfg, causal=True, positions=positions)
            h = rms_norm(x, bp["ln2"], cfg.norm_eps)
            y, a = moe_mod.moe_block(h, bp["moe"], cfg, groups)
            x = hint(x + y, "batch", "seq", "embed")
            return (x, aux + a), None
        (x, aux), _ = scan_blocks(_wrap_remat(body, remat), (x, aux0),
                                  params["blocks"], scan_layers)

    elif fam == "vlm":
        img = batch["image_embeds"].astype(x.dtype)

        def body(carry, bp):
            def inner(c, ip):
                return _attn_block(c, ip, cfg, causal=True, positions=positions,
                                   ff_fn=lambda h: mlp_apply(h, ip["mlp"], cfg)), None
            c, _ = scan_blocks(inner, carry, bp["self"], scan_layers)
            return _cross_block(c, bp["cross"], cfg, img=img), None
        x, _ = scan_blocks(_wrap_remat(body, remat), x, params["blocks"], scan_layers)
        aux = aux0

    elif fam == "ssm":
        def body(carry, bp):
            return _ssm_block(carry, bp, cfg), None
        x, _ = scan_blocks(_wrap_remat(body, remat), x, params["blocks"], scan_layers)
        aux = aux0

    elif fam == "hybrid":
        shared = params["shared_attn"]

        def body(carry, bp):
            def inner(c, ip):
                return _ssm_block(c, ip, cfg), None
            c, _ = scan_blocks(inner, carry, bp["ssm"], scan_layers)
            c = _attn_block(c, shared, cfg, causal=True, positions=positions,
                            ff_fn=lambda h: mlp_apply(h, shared["mlp"], cfg))
            return c, None
        x, _ = scan_blocks(_wrap_remat(body, remat), x, params["blocks"], scan_layers)
        aux = aux0
    else:
        raise ValueError(fam)

    if last_only:
        x = x[:, -1:, :]
    with jax.named_scope("head"):
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        # logits stay in the model dtype; cross_entropy does fp32 logsumexp
        # internally.  (§Perf iteration D8: a preferred_element_type=f32 here
        # made every backward cotangent fp32, doubling gradient all-reduce and
        # activation-gradient traffic model-wide.)
        logits = jnp.einsum("bsd,dv->bsv", x, params["unembed"])
        logits = hint(logits, "batch", "seq", "vocab")
    return logits.astype(jnp.float32) if last_only else logits, aux


def loss_fn(params, batch, cfg: ModelConfig, *, remat: str = "none",
            scan_layers: bool = True):
    logits, aux = forward(params, batch, cfg, remat=remat, scan_layers=scan_layers)
    with jax.named_scope("loss"):
        loss = cross_entropy(logits, batch["labels"], batch.get("mask"))
    total = loss + AUX_COEF * aux
    return total, {"loss": loss, "aux": aux}


# ============================================================= cache =========

def _kv_cache_leaf(cfg, n, b, s, dtype, stack=()):
    m, k = cfg.kv_heads, cfg.hdim
    shape = tuple(stack) + (b, s, m, k)
    axes = tuple("layers" for _ in stack) + ("batch", "kv_seq", "kv_heads", "head_dim")
    return shape, axes, dtype


def cache_struct(cfg: ModelConfig, batch: int, max_seq: int):
    """-> pytree of (shape, logical_axes, dtype) describing the decode cache."""
    dt = jnp.dtype(cfg.dtype)
    fam = cfg.family
    if fam == "dense":
        kv = _kv_cache_leaf(cfg, cfg.num_layers, batch, max_seq, dt,
                            (cfg.num_layers,))
        return {"k": kv, "v": kv}
    if fam == "moe":
        nl = cfg.num_layers
        if cfg.use_mla:
            r, dr = cfg.kv_lora_rank, cfg.qk_rope_head_dim
            return {
                "ckv": ((nl, batch, max_seq, r),
                        ("layers", "batch", "kv_seq", "lora"), dt),
                "krope": ((nl, batch, max_seq, dr),
                          ("layers", "batch", "kv_seq", "head_dim"), dt),
            }
        kv = _kv_cache_leaf(cfg, nl, batch, max_seq, dt, (nl,))
        return {"k": kv, "v": kv}
    if fam == "vlm":
        g = cfg.num_layers // cfg.cross_attn_every
        inner = cfg.cross_attn_every - 1
        m, k = cfg.kv_heads, cfg.hdim
        kv = ((g, inner, batch, max_seq, m, k),
              ("layers", "layers", "batch", "kv_seq", "kv_heads", "head_dim"), dt)
        xkv = ((g, batch, cfg.num_image_tokens, m, k),
               ("layers", "batch", "img_seq", "kv_heads", "head_dim"), dt)
        return {"k": kv, "v": kv, "xk": xkv, "xv": xkv}
    if fam == "ssm":
        nl, w = cfg.num_layers, cfg.conv_width
        return {
            "conv_x": ((nl, batch, w - 1, cfg.d_inner),
                       ("layers", "batch", "conv", "ff"), dt),
            "conv_B": ((nl, batch, w - 1, cfg.ssm_state),
                       ("layers", "batch", "conv", "state"), dt),
            "conv_C": ((nl, batch, w - 1, cfg.ssm_state),
                       ("layers", "batch", "conv", "state"), dt),
            "state": ((nl, batch, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state),
                      ("layers", "batch", "heads", None, "state"), jnp.float32),
        }
    if fam == "hybrid":
        g = cfg.num_layers // cfg.attn_every
        k = cfg.attn_every
        w = cfg.conv_width
        m, hd = cfg.kv_heads, cfg.hdim
        return {
            "conv_x": ((g, k, batch, w - 1, cfg.d_inner),
                       ("layers", "layers", "batch", "conv", "ff"), dt),
            "conv_B": ((g, k, batch, w - 1, cfg.ssm_state),
                       ("layers", "layers", "batch", "conv", "state"), dt),
            "conv_C": ((g, k, batch, w - 1, cfg.ssm_state),
                       ("layers", "layers", "batch", "conv", "state"), dt),
            "state": ((g, k, batch, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state),
                      ("layers", "layers", "batch", "heads", None, "state"),
                      jnp.float32),
            "attn_k": ((g, batch, max_seq, m, hd),
                       ("layers", "batch", "kv_seq", "kv_heads", "head_dim"), dt),
            "attn_v": ((g, batch, max_seq, m, hd),
                       ("layers", "batch", "kv_seq", "kv_heads", "head_dim"), dt),
        }
    raise ValueError(f"{fam} has no decode cache")


def _is_leaf(x):
    return isinstance(x, tuple) and len(x) == 3 and isinstance(x[0], tuple)


def init_cache(cfg: ModelConfig, batch: int, max_seq: int, *, abstract=False):
    st = cache_struct(cfg, batch, max_seq)
    if abstract:
        return jax.tree.map(lambda t: jax.ShapeDtypeStruct(t[0], t[2]), st,
                            is_leaf=_is_leaf)
    return jax.tree.map(lambda t: jnp.zeros(t[0], t[2]), st, is_leaf=_is_leaf)


def cache_axes(cfg: ModelConfig, batch: int = 1, max_seq: int = 8):
    return jax.tree.map(lambda t: t[1], cache_struct(cfg, batch, max_seq),
                        is_leaf=_is_leaf)


# ============================================================ decode =========

def prime_cross_cache(params, cache, image_embeds, cfg: ModelConfig):
    """VLM: fill the per-group cross-attention K/V from the image embeddings.

    Must be called once before decode (the cross K/V are position-independent,
    so they are computed exactly once, not per decode step).
    """
    assert cfg.family == "vlm"
    img = image_embeds.astype(jnp.dtype(cfg.dtype))

    def one(bp):
        cp = bp["cross"]
        k = jnp.einsum("btd,dmk->btmk", img, cp["attn"]["wk"])
        v = jnp.einsum("btd,dmk->btmk", img, cp["attn"]["wv"])
        return k, v

    ks, vs = jax.vmap(one)(params["blocks"])
    cache = dict(cache)
    cache["xk"] = ks.astype(cache["xk"].dtype)
    cache["xv"] = vs.astype(cache["xv"].dtype)
    return cache


def scan_decode(body, x0, xs, cache):
    """scan over layers with the cache as an IN-PLACE carry.

    ``body(x, xs_i, cache_slice) -> (x, new_cache_slice)``; cache leaves are
    stacked (L, ...).  Carrying the full cache and dynamic-update-slicing at
    the layer index keeps XLA's while-carry aliasing in place -- the
    xs->ys formulation double-buffered the whole multi-GB cache every layer
    (42 % of decode HBM traffic for llama3-405b; §Perf decode diagnosis).
    Read-only per-layer tensors belong in ``xs`` instead.
    """
    leaves, tdef = jax.tree.flatten(cache)

    def f(carry, xs_i):
        x, cl, i = carry
        sl = [jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False)
              for a in cl]
        x, new_slice = body(x, xs_i, jax.tree.unflatten(tdef, sl))
        new_leaves = tdef.flatten_up_to(new_slice)
        cl = [jax.lax.dynamic_update_index_in_dim(a, ns.astype(a.dtype), i, 0)
              for a, ns in zip(cl, new_leaves)]
        return (x, cl, i + 1), None

    (x, leaves, _), _ = jax.lax.scan(f, (x0, leaves, jnp.int32(0)), xs)
    return x, jax.tree.unflatten(tdef, leaves)


def _attn_block_decode(x, p, cfg, ck, cv, start):
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    with jax.named_scope("attn"):
        a, ck, cv = attn.gqa_decode(h, p["attn"], cfg, ck, cv, start)
    x = x + a
    h = rms_norm(x, p["ln2"], cfg.norm_eps)
    return x + mlp_apply(h, p["mlp"], cfg), ck, cv


def _dense_cached(params, cache, x, start, cfg):
    """The dense layers over x (b,C,d) at positions start .. start+C-1
    against the {"k","v"} cache: a generated token (C = 1) or a prefill
    chunk.  -> (x, new cache)."""
    def body(carry, bp, sl):
        y, ck, cv = _attn_block_decode(carry, bp, cfg, sl["k"], sl["v"], start)
        return y, {"k": ck, "v": cv}
    return scan_decode(body, x, params["blocks"],
                       {"k": cache["k"], "v": cache["v"]})


def _head(params, x, cfg):
    """Final norm and fp32 unembedding of x (b,1,d) -> logits (b,v)."""
    with jax.named_scope("head"):
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = jnp.einsum("bsd,dv->bsv", x, params["unembed"],
                            preferred_element_type=jnp.float32)
    return logits[:, 0, :]


def _ssm_block_decode(x1, p, cfg, cache):
    h = rms_norm(x1, p["ln"], cfg.norm_eps)
    y, new_cache = ssm_mod.mamba2_block(h, p["mixer"], cfg, cache=cache,
                                        single_step=True)
    return x1 + y, new_cache


def decode_step(params, cache, token, pos, cfg: ModelConfig):
    """token (b,) int32; pos scalar int32 -> (logits (b,v) fp32, new cache)."""
    fam = cfg.family
    x = jnp.take(params["embed"], token[:, None], axis=0)  # (b,1,d)

    if fam == "dense":
        x, cache = _dense_cached(params, cache, x, pos, cfg)

    elif fam == "moe":
        groups = 1
        if cfg.first_k_dense:
            def fbody(carry, bp, sl):
                h = rms_norm(carry, bp["ln1"], cfg.norm_eps)
                a, ckv, kr = attn.mla_decode(h, bp["attn"], cfg, sl["ckv"],
                                             sl["krope"], pos)
                carry = carry + a
                h = rms_norm(carry, bp["ln2"], cfg.norm_eps)
                return (carry + mlp_apply(h, bp["mlp"], cfg),
                        {"ckv": ckv, "krope": kr})
            nf = cfg.first_k_dense
            x, first_c = scan_decode(fbody, x, params["first"],
                                     {"ckv": cache["ckv"][:nf],
                                      "krope": cache["krope"][:nf]})

        def body(carry, bp, sl):
            h = rms_norm(carry, bp["ln1"], cfg.norm_eps)
            if cfg.use_mla:
                a, c1, c2 = attn.mla_decode(h, bp["attn"], cfg, sl["a"],
                                            sl["b"], pos)
            else:
                a, c1, c2 = attn.gqa_decode(h, bp["attn"], cfg, sl["a"],
                                            sl["b"], pos)
            carry = carry + a
            h = rms_norm(carry, bp["ln2"], cfg.norm_eps)
            y, _ = moe_mod.moe_block(h, bp["moe"], cfg, groups)
            return carry + y, {"a": c1, "b": c2}

        if cfg.use_mla:
            nf = cfg.first_k_dense
            x, main_c = scan_decode(body, x, params["blocks"],
                                    {"a": cache["ckv"][nf:],
                                     "b": cache["krope"][nf:]})
            if cfg.first_k_dense:
                cache = {"ckv": jnp.concatenate([first_c["ckv"], main_c["a"]]),
                         "krope": jnp.concatenate([first_c["krope"],
                                                   main_c["b"]])}
            else:
                cache = {"ckv": main_c["a"], "krope": main_c["b"]}
        else:
            x, main_c = scan_decode(body, x, params["blocks"],
                                    {"a": cache["k"], "b": cache["v"]})
            cache = {"k": main_c["a"], "v": main_c["b"]}

    elif fam == "vlm":
        def body(carry, xs, sl):
            bp, xk, xv = xs

            def inner(c, ip, isl):
                y, ick, icv = _attn_block_decode(c, ip, cfg, isl["k"],
                                                 isl["v"], pos)
                return y, {"k": ick, "v": icv}
            c, new_inner = scan_decode(inner, carry, bp["self"],
                                       {"k": sl["k"], "v": sl["v"]})
            # cross-attn against cached image K/V
            cp = bp["cross"]
            h = rms_norm(c, cp["ln1"], cfg.norm_eps)
            b = h.shape[0]
            q = jnp.einsum("bsd,dhk->bshk", h, cp["attn"]["wq"])
            m = cfg.kv_heads
            g = cfg.num_heads // m
            qg = q.reshape(b, m, g, cfg.hdim)
            sc = jnp.einsum("bmgk,btmk->bmgt", qg, xk) / (cfg.hdim ** 0.5)
            pr = jax.nn.softmax(sc.astype(jnp.float32), axis=-1).astype(xv.dtype)
            o = jnp.einsum("bmgt,btmv->bmgv", pr, xv)
            o = o.reshape(b, 1, cfg.num_heads, cfg.hdim)
            c = c + jnp.einsum("bshk,hkd->bsd", o, cp["attn"]["wo"])
            h = rms_norm(c, cp["ln2"], cfg.norm_eps)
            c = c + mlp_apply(h, cp["mlp"], cfg)
            return c, new_inner
        x, new_kv = scan_decode(
            body, x, (params["blocks"], cache["xk"], cache["xv"]),
            {"k": cache["k"], "v": cache["v"]})
        cache = {"k": new_kv["k"], "v": new_kv["v"],
                 "xk": cache["xk"], "xv": cache["xv"]}

    elif fam == "ssm":
        def body(carry, bp, sl):
            return _ssm_block_decode(carry, bp, cfg, sl)
        x, cache = scan_decode(
            body, x, params["blocks"],
            {k: cache[k] for k in ("conv_x", "conv_B", "conv_C", "state")})

    elif fam == "hybrid":
        shared = params["shared_attn"]

        def body(carry, bp, sl):
            def inner(c, ip, isl):
                return _ssm_block_decode(c, ip, cfg, isl)
            ssm_sl = {k: sl[k] for k in ("conv_x", "conv_B", "conv_C",
                                         "state")}
            c, n_ssm = scan_decode(inner, carry, bp["ssm"], ssm_sl)
            y, ck, cv = _attn_block_decode(c, shared, cfg, sl["attn_k"],
                                           sl["attn_v"], pos)
            n_ssm.update({"attn_k": ck, "attn_v": cv})
            return y, n_ssm
        x, cache = scan_decode(body, x, params["blocks"],
                               {k: cache[k] for k in cache})
    else:
        raise ValueError(f"{fam} does not support decode")
    return _head(params, x, cfg), cache


def chunked_prefill(cfg: ModelConfig) -> bool:
    """Whether a prompt goes in through ``prefill_chunk``: the dense family,
    whose decode block is ``gqa_decode`` over a {"k","v"} cache.  MoE's
    capacity dispatch would route b*C tokens otherwise than b; MLA, SSM,
    hybrid and VLM blocks have no chunk capture."""
    return cfg.family == "dense"


def prefill_chunk(params, cache, tokens, start, last, cfg: ModelConfig):
    """tokens (b,C) int32 at positions start .. start+C-1 into the cache;
    start, last scalar int32 -> (logits (b,v) fp32 of chunk row ``last``,
    new cache).  Rows past the prompt may hold anything: decode overwrites
    each position before any query can see it."""
    if not chunked_prefill(cfg):
        raise NotImplementedError(f"chunked prefill for family {cfg.family!r}")
    x = jnp.take(params["embed"], tokens, axis=0)  # (b,C,d)
    x, cache = _dense_cached(params, cache, x, start, cfg)
    return _head(params, jax.lax.dynamic_slice_in_dim(x, last, 1, axis=1),
                 cfg), cache
