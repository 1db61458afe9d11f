"""Attention blocks: GQA (causal / bidirectional / cross), MLA, decode paths.

Causal GQA self-attention on a TPU (training, and any full-sequence forward)
runs as JAX's splash attention kernel: blockwise online softmax that skips
the blocks above the diagonal, with its own dq/dkv backward, so the s x s
score square never reaches HBM (``flash_ok`` says when).  Every other call
takes plain jnp: ``_sdpa`` materializes the fp32 scores, and sequences longer
than ``CHUNK_THRESHOLD`` go through ``_sdpa_chunked``, which maps over query
chunks.  The in-repo forward-only kernel ``repro.kernels.flash_attention`` is
on no step's path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu import splash_attention as splash
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.distributed.sharding import current, hint
from repro.models.common import rope, spec, softmax_fp32

import os

# seqs longer than this use the chunked (flash-style) path; below it the
# plain einsum path avoids lax.map slicing a sharded seq dim (which forces
# GSPMD into "involuntary full rematerialization" replication -- see
# EXPERIMENTS.md §Perf iteration L1)
CHUNK_THRESHOLD = int(os.environ.get("REPRO_ATTN_CHUNK_THRESHOLD", 8192))
Q_CHUNK = int(os.environ.get("REPRO_ATTN_Q_CHUNK", 1024))

# The splash kernel's query and key blocks, forward and backward: the largest
# of FLASH_BLOCK, FLASH_BLOCK/2, ... down to FLASH_MIN_BLOCK that divides the
# sequence; dq and dk/dv come from separate backward kernels.  Swept on a v5e
# at smollm-360m's train shapes (scripts/flash_block_sweep.py, PERF.md §6).
FLASH_BLOCK = 512
FLASH_MIN_BLOCK = 128


# ------------------------------------------------------------------ specs ----

def gqa_spec(cfg: ModelConfig, d_model: int | None = None):
    d = d_model or cfg.d_model
    h, m, k = cfg.num_heads, cfg.kv_heads, cfg.hdim
    return {
        "wq": spec((d, h, k), ("embed", "heads", "head_dim"), d ** -0.5),
        "wk": spec((d, m, k), ("embed", "kv_heads", "head_dim"), d ** -0.5),
        "wv": spec((d, m, k), ("embed", "kv_heads", "head_dim"), d ** -0.5),
        "wo": spec((h, k, d), ("heads", "head_dim", "embed"),
                   (h * k) ** -0.5 / (2 * cfg.num_layers) ** 0.5),
    }


def mla_spec(cfg: ModelConfig):
    d, h = cfg.d_model, cfg.num_heads
    r, dr, dn, dv = cfg.kv_lora_rank, cfg.qk_rope_head_dim, cfg.qk_nope_head_dim, cfg.v_head_dim
    return {
        "wq": spec((d, h, dn + dr), ("embed", "heads", "head_dim"), d ** -0.5),
        "w_kv_down": spec((d, r + dr), ("embed", "lora"), d ** -0.5),
        "w_k_up": spec((r, h, dn), ("lora", "heads", "head_dim"), r ** -0.5),
        "w_v_up": spec((r, h, dv), ("lora", "heads", "head_dim"), r ** -0.5),
        "wo": spec((h, dv, d), ("heads", "head_dim", "embed"),
                   (h * dv) ** -0.5 / (2 * cfg.num_layers) ** 0.5),
    }


# ----------------------------------------------------------------- core ------

def _sdpa(q, k, v, *, causal: bool, q_pos0: int = 0):
    """q (b,s,h,dk), k/v (b,t,m,dk|dv) -> (b,s,h,dv); GQA by head grouping.

    Wrapped in named_scope("flashrgn"), a marker for the dry-run analyzer
    (``distributed/hlo_analysis.py``): it substitutes the Pallas flash
    kernel's HBM I/O (kernels/flash_attention, validated vs this exact math)
    for the score materialization of this jnp lowering.  This region runs as
    plain XLA ops; on a TPU, causal GQA self-attention does not reach it but
    runs as the splash kernel (``flash_sdpa``), so the analyzer's substitution
    holds for CPU lowerings only.
    """
    with jax.named_scope("flashrgn"):
        b, s, h, dk = q.shape
        t, m = k.shape[1], k.shape[2]
        g = h // m
        qg = q.reshape(b, s, m, g, dk)
        scores = jnp.einsum("bsmgk,btmk->bmgst", qg, k) / (dk ** 0.5)
        if causal:
            qp = jnp.arange(s) + q_pos0
            kp = jnp.arange(t)
            mask = qp[:, None] >= kp[None, :]
            probs = softmax_fp32(scores, where=mask[None, None, None])
        else:
            probs = softmax_fp32(scores)
        out = jnp.einsum("bmgst,btmv->bsmgv", probs.astype(v.dtype), v)
        return out.reshape(b, s, h, v.shape[-1])


def _sdpa_chunked(q, k, v, *, causal: bool, q_chunk: int = Q_CHUNK):
    """Flash-style: lax.map over query chunks; scores never exceed (b,m,g,qc,t)."""
    b, s, h, dk = q.shape
    if s % q_chunk != 0 or s <= q_chunk:
        return _sdpa(q, k, v, causal=causal)
    n = s // q_chunk
    qc = q.reshape(b, n, q_chunk, h, dk).transpose(1, 0, 2, 3, 4)  # (n,b,qc,h,dk)

    def one(args):
        i, qi = args
        return _sdpa(qi, k, v, causal=causal, q_pos0=i * q_chunk)

    outs = jax.lax.map(one, (jnp.arange(n), qc))                   # (n,b,qc,h,dv)
    return outs.transpose(1, 0, 2, 3, 4).reshape(b, s, h, v.shape[-1])


def sdpa(q, k, v, *, causal: bool):
    """q (b,s,h,dk), k/v (b,t,m,dk|dv) -> (b,s,h,dv).  Causal self-attention
    on a TPU runs as the splash kernel where ``flash_ok`` holds; every other
    call takes the jnp paths."""
    if flash_ok(q, k, v, causal=causal):
        with jax.named_scope("flash"):
            return flash_sdpa(q, k, v)
    if q.shape[1] > CHUNK_THRESHOLD:
        return _sdpa_chunked(q, k, v, causal=causal)
    return _sdpa(q, k, v, causal=causal)


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _flash_block(s: int) -> int:
    """The kernel's block for sequence length s; 0 where none divides it."""
    blk = FLASH_BLOCK
    while blk >= FLASH_MIN_BLOCK and s % blk:
        blk //= 2
    return blk if blk >= FLASH_MIN_BLOCK else 0


def flash_ok(q, k, v, *, causal: bool) -> bool:
    """Whether ``sdpa`` of these operands runs as the splash kernel: on a
    TPU, causal self-attention (queries and keys over the same s positions,
    both from position 0), K and V of one head dim, s a multiple of a kernel
    block, and no mesh "model" axis over 1 (the kernel splits only over the
    batch)."""
    ctx = current()
    model = 1 if ctx is None else ctx.mesh.shape.get("model", 1)
    s = q.shape[1]
    return (_on_tpu() and causal and k.shape[1] == s
            and k.shape[-1] == v.shape[-1] and _flash_block(s) > 0
            and model == 1)


def block_sizes(blk: int, *, fused: bool = False) -> splash.BlockSizes:
    """Every query and key block of the kernel, forward and backward, blk;
    dq and dk/dv from separate backward kernels, or from one if ``fused``."""
    dq = {} if fused else {"block_q_dq": blk, "block_kv_dq": blk}
    return splash.BlockSizes(
        block_q=blk, block_kv=blk, block_kv_compute=blk, block_q_dkv=blk,
        block_kv_dkv=blk, block_kv_dkv_compute=blk, use_fused_bwd_kernel=fused,
        **dq)


@functools.lru_cache(maxsize=None)
def splash_kernel(g: int, s: int, blocks: splash.BlockSizes, interpret: bool):
    """One KV head's kernel, heads first: g query heads (g,s,dk) over one K/V
    (s,dk), causal, unscaled.  Its mask tables are made as constants even
    when the first call comes inside a trace, since the cache hands them to
    later traces."""
    mask = splash.MultiHeadMask([splash.CausalMask((s, s))] * g)
    with jax.ensure_compile_time_eval():
        return splash.make_splash_mqa(mask, block_sizes=blocks, head_shards=1,
                                      q_seq_shards=1, interpret=interpret)


def flash_sdpa(q, k, v, *, interpret: bool = False):
    """Causal GQA self-attention through the splash kernel, in ``_sdpa``'s
    layout: q (b,s,h,dk), k/v (b,s,m,dk) -> (b,s,h,dk).  One MQA kernel per
    KV head over its g = h/m query heads, heads first, mapped over batch and
    KV heads; the scale is folded into q.  Under a mesh of several devices the
    kernel runs inside ``shard_map`` over the batch axes, so q, k and v stay
    where they are; mesh axes that an enclosing ``shard_map`` already holds
    manual (local SGD's "pod") stay outside it."""
    b, s, h, dk = q.shape
    m = k.shape[2]
    kernel = jax.vmap(jax.vmap(splash_kernel(
        h // m, s, block_sizes(_flash_block(s)), interpret)))

    def run(q, k, v):
        def heads_first(x):
            return x.transpose(0, 2, 1, 3)
        qg = heads_first(q * dk ** -0.5).reshape(-1, m, h // m, s, dk)
        out = kernel(qg, heads_first(k), heads_first(v))
        return heads_first(out.reshape(-1, h, s, dk))

    ctx = current()
    if ctx is None or ctx.mesh.size == 1:
        return run(q, k, v)
    outer = jax.sharding.get_abstract_mesh()
    manual = set(outer.manual_axes)
    mesh = outer if manual else ctx.mesh
    part = P(ctx.fit_axes(b, ctx.map["batch"]), None, None, None)
    return jax.shard_map(run, mesh=mesh, in_specs=(part, part, part),
                         out_specs=part, axis_names=set(mesh.axis_names) - manual,
                         check_vma=False)(q, k, v)


# ------------------------------------------------------------- GQA block -----

def gqa_attention(x, p, cfg: ModelConfig, *, causal: bool, positions,
                  kv_src=None, use_rope: bool = True):
    """Self- or cross-attention. kv_src: source sequence for cross-attn."""
    src = x if kv_src is None else kv_src
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("btd,dmk->btmk", src, p["wk"])
    v = jnp.einsum("btd,dmk->btmk", src, p["wv"])
    q = hint(q, "batch", None, "heads", "head_dim")
    k = hint(k, "batch", None, "kv_heads", "head_dim")
    if use_rope:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    out = sdpa(q, k, v, causal=causal)
    out = hint(out, "batch", None, "heads", "head_dim")
    # seq-sharded output -> reduce-scatter for the TP partial sum (§Perf L3)
    return hint(jnp.einsum("bshk,hkd->bsd", out, p["wo"]),
                "batch", "seq", "embed")


def gqa_prefill_kv(x, p, cfg: ModelConfig, *, positions, use_rope: bool = True):
    """K/V as stored in the decode cache."""
    k = jnp.einsum("btd,dmk->btmk", x, p["wk"])
    v = jnp.einsum("btd,dmk->btmk", x, p["wv"])
    if use_rope:
        k = rope(k, positions, cfg.rope_theta)
    return k, v


def gqa_decode(x1, p, cfg: ModelConfig, cache_k, cache_v, pos, *,
               update_cache: bool = True, use_rope: bool = True):
    """One-token decode. x1 (b,1,d); cache_k/v (b,S,m,dk). pos: scalar int."""
    b, _, d = x1.shape
    S, m = cache_k.shape[1], cache_k.shape[2]
    q = jnp.einsum("bsd,dhk->bshk", x1, p["wq"])
    if use_rope:
        q = rope(q, jnp.full((1,), pos), cfg.rope_theta)
    if update_cache:
        k1 = jnp.einsum("bsd,dmk->bsmk", x1, p["wk"])
        v1 = jnp.einsum("bsd,dmk->bsmk", x1, p["wv"])
        if use_rope:
            k1 = rope(k1, jnp.full((1,), pos), cfg.rope_theta)
        cache_k = jax.lax.dynamic_update_slice(cache_k, k1.astype(cache_k.dtype),
                                               (0, pos, 0, 0))
        cache_v = jax.lax.dynamic_update_slice(cache_v, v1.astype(cache_v.dtype),
                                               (0, pos, 0, 0))
    h, dk = q.shape[2], q.shape[3]
    g = h // m
    qg = q.reshape(b, m, g, dk)
    cache_k = hint(cache_k, "batch", "kv_seq", "kv_heads", "head_dim")
    cache_v = hint(cache_v, "batch", "kv_seq", "kv_heads", "head_dim")
    scores = jnp.einsum("bmgk,btmk->bmgt", qg, cache_k) / (dk ** 0.5)
    valid = jnp.arange(S)[None, None, None, :] <= pos
    probs = softmax_fp32(scores, where=valid)
    out = jnp.einsum("bmgt,btmv->bmgv", probs.astype(cache_v.dtype), cache_v)
    out = out.reshape(b, 1, h, cache_v.shape[-1])
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"]), cache_k, cache_v


def gqa_prefill_chunk(x, p, cfg: ModelConfig, cache_k, cache_v, start):
    """``gqa_decode`` for C tokens at positions start .. start+C-1.

    x (b,C,d); cache_k/v (b,S,m,dk); start: scalar int.  Writes the chunk's
    K/V at ``start``, then scores each query against the whole cache, masked
    to the positions at or before its own."""
    b, c, _ = x.shape
    S, m = cache_k.shape[1], cache_k.shape[2]
    positions = start + jnp.arange(c)
    q = rope(jnp.einsum("bsd,dhk->bshk", x, p["wq"]), positions, cfg.rope_theta)
    k, v = gqa_prefill_kv(x, p, cfg, positions=positions)
    cache_k = jax.lax.dynamic_update_slice(cache_k, k.astype(cache_k.dtype),
                                           (0, start, 0, 0))
    cache_v = jax.lax.dynamic_update_slice(cache_v, v.astype(cache_v.dtype),
                                           (0, start, 0, 0))
    h, dk = q.shape[2], q.shape[3]
    qg = q.reshape(b, c, m, h // m, dk)
    cache_k = hint(cache_k, "batch", "kv_seq", "kv_heads", "head_dim")
    cache_v = hint(cache_v, "batch", "kv_seq", "kv_heads", "head_dim")
    scores = jnp.einsum("bsmgk,btmk->bmgst", qg, cache_k) / (dk ** 0.5)
    valid = jnp.arange(S)[None, :] <= positions[:, None]
    probs = softmax_fp32(scores, where=valid[None, None, None])
    out = jnp.einsum("bmgst,btmv->bsmgv", probs.astype(cache_v.dtype), cache_v)
    out = out.reshape(b, c, h, cache_v.shape[-1])
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"]), cache_k, cache_v


# ------------------------------------------------------------- MLA block -----

def _mla_qkv(x, p, cfg: ModelConfig, positions):
    dn, dr = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    r = cfg.kv_lora_rank
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = rope(q_rope, positions, cfg.rope_theta)
    down = jnp.einsum("bsd,dr->bsr", x, p["w_kv_down"])
    c_kv, k_rope = down[..., :r], down[..., r:]
    k_rope = rope(k_rope[:, :, None, :], positions, cfg.rope_theta)[:, :, 0, :]
    return q_nope, q_rope, c_kv, k_rope


def mla_attention(x, p, cfg: ModelConfig, *, causal: bool, positions):
    """Training/prefill MLA: materialize per-head K/V from the latent."""
    q_nope, q_rope, c_kv, k_rope = _mla_qkv(x, p, cfg, positions)
    k_nope = jnp.einsum("bsr,rhk->bshk", c_kv, p["w_k_up"])
    v = jnp.einsum("bsr,rhv->bshv", c_kv, p["w_v_up"])
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    b, s = x.shape[0], x.shape[1]
    h = cfg.num_heads
    k_rope_b = jnp.broadcast_to(k_rope[:, :, None, :], (b, s, h, cfg.qk_rope_head_dim))
    k = jnp.concatenate([k_nope, k_rope_b], axis=-1)
    out = sdpa(q, k, v, causal=causal)
    return hint(jnp.einsum("bshv,hvd->bsd", out, p["wo"]),
                "batch", "seq", "embed")


def mla_decode(x1, p, cfg: ModelConfig, cache_ckv, cache_krope, pos):
    """Absorbed-projection MLA decode: attend in the latent space.

    cache_ckv (b,S,r); cache_krope (b,S,dr).  W_uk is absorbed into the query
    (q_lat = q_nope @ W_uk) so scores are computed directly against the cached
    latent -- the deployment trick from the DeepSeek-V2 paper.
    """
    q_nope, q_rope, c_kv1, k_rope1 = _mla_qkv(
        x1, p, cfg, jnp.full((1,), pos))
    cache_ckv = jax.lax.dynamic_update_slice(
        cache_ckv, c_kv1.astype(cache_ckv.dtype), (0, pos, 0))
    cache_krope = jax.lax.dynamic_update_slice(
        cache_krope, k_rope1.astype(cache_krope.dtype), (0, pos, 0))
    b = x1.shape[0]
    S = cache_ckv.shape[1]
    dn, dr, r = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.kv_lora_rank
    q_lat = jnp.einsum("bshk,rhk->bshr", q_nope, p["w_k_up"])      # absorb W_uk
    scores = (jnp.einsum("bshr,btr->bhst", q_lat, cache_ckv)
              + jnp.einsum("bshk,btk->bhst", q_rope, cache_krope)) / ((dn + dr) ** 0.5)
    valid = (jnp.arange(S)[None, None, None, :] <= pos)
    probs = softmax_fp32(scores, where=valid)
    out_lat = jnp.einsum("bhst,btr->bshr", probs.astype(cache_ckv.dtype), cache_ckv)
    out = jnp.einsum("bshr,rhv->bshv", out_lat, p["w_v_up"])       # absorb W_uv
    return jnp.einsum("bshv,hvd->bsd", out, p["wo"]), cache_ckv, cache_krope
