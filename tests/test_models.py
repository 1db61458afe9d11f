"""Model-zoo correctness: decode-vs-forward consistency (the strongest cache
test), SSD chunked-vs-recurrence, MLA absorbed decode, conv cache, and the
splash kernel path of causal self-attention against the jnp path."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AbstractMesh

from repro.configs import ARCH_IDS, get_reduced
from repro.distributed.sharding import ShardingCtx, use_sharding
from repro.launch.specs import make_batch
from repro.models import attention, build_model
from repro.models.common import init_params
from repro.models.ssm import _conv1d_causal, ssd_scan

DECODE_ARCHS = [n for n in ARCH_IDS if n != "hubert-xlarge"]


def _fp32(arch):
    return arch.replace(model=arch.model.replace(dtype="float32"))


@pytest.mark.parametrize("name", DECODE_ARCHS)
def test_decode_matches_forward(name):
    """Teacher-forced decode through the cache must reproduce the training
    forward logits position by position (fp32).

    MoE archs run with a large capacity factor: capacity-based dispatch
    DROPS overflow tokens under load in the batched forward, while one-token
    decode never overflows -- that (designed) difference is exactly what
    this test would otherwise flag (and did, during development).
    """
    arch = _fp32(get_reduced(name))
    if arch.model.num_experts:
        arch = arch.replace(model=arch.model.replace(capacity_factor=8.0))
    model = build_model(arch)
    params = model.init(jax.random.key(0))
    b, s = 2, 12
    batch = make_batch(arch, b, s, seed=3)
    ref_logits, _ = model.forward(params, batch)          # (b, s, v)

    cache = model.init_cache(b, s)
    if arch.model.family == "vlm":
        cache = model.prime_cross_cache(params, cache, batch["image_embeds"])
    errs = []
    for t in range(s):
        step_logits, cache = model.decode_step(
            params, cache, batch["tokens"][:, t], jnp.int32(t))
        errs.append(float(jnp.max(jnp.abs(step_logits - ref_logits[:, t]))))
    assert max(errs) < 2e-2, f"{name}: decode/forward divergence {max(errs)}"


def test_ssd_chunked_matches_recurrence():
    rng = np.random.default_rng(0)
    b, s, h, p, n = 2, 64, 3, 8, 4
    x = jnp.asarray(rng.standard_normal((b, s, h, p)), jnp.float32)
    dt = jnp.asarray(np.abs(rng.standard_normal((b, s, h))) * 0.2, jnp.float32)
    a_log = jnp.asarray(rng.standard_normal(h) * 0.3, jnp.float32)
    B = jnp.asarray(rng.standard_normal((b, s, n)), jnp.float32)
    C = jnp.asarray(rng.standard_normal((b, s, n)), jnp.float32)

    y16, st16 = ssd_scan(x, dt, a_log, B, C, chunk=16)
    y64, st64 = ssd_scan(x, dt, a_log, B, C, chunk=64)
    np.testing.assert_allclose(y16, y64, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(st16, st64, rtol=1e-4, atol=1e-4)

    # exact sequential recurrence
    A = -jnp.exp(a_log)
    st = jnp.zeros((b, h, p, n))
    ys = []
    for t in range(s):
        dec = jnp.exp(dt[:, t] * A)[..., None, None]
        upd = jnp.einsum("bh,bn,bhp->bhpn", dt[:, t], B[:, t], x[:, t])
        st = st * dec + upd
        ys.append(jnp.einsum("bn,bhpn->bhp", C[:, t], st))
    y_ref = jnp.stack(ys, axis=1)
    np.testing.assert_allclose(y16, y_ref, rtol=1e-4, atol=1e-4)


def test_ssd_init_state_continuation():
    """Scanning [first half] then [second half from carried state] must equal
    one full scan -- the property decode and prefill-chunking rely on."""
    rng = np.random.default_rng(1)
    b, s, h, p, n = 1, 64, 2, 8, 4
    x = jnp.asarray(rng.standard_normal((b, s, h, p)), jnp.float32)
    dt = jnp.asarray(np.abs(rng.standard_normal((b, s, h))) * 0.2, jnp.float32)
    a_log = jnp.asarray(rng.standard_normal(h) * 0.3, jnp.float32)
    B = jnp.asarray(rng.standard_normal((b, s, n)), jnp.float32)
    C = jnp.asarray(rng.standard_normal((b, s, n)), jnp.float32)
    y_full, st_full = ssd_scan(x, dt, a_log, B, C, chunk=16)
    m = s // 2
    y1, st1 = ssd_scan(x[:, :m], dt[:, :m], a_log, B[:, :m], C[:, :m], 16)
    y2, st2 = ssd_scan(x[:, m:], dt[:, m:], a_log, B[:, m:], C[:, m:], 16,
                       init_state=st1)
    np.testing.assert_allclose(jnp.concatenate([y1, y2], 1), y_full,
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(st2, st_full, rtol=1e-4, atol=1e-4)


def test_conv_cache_streaming():
    """Streaming 1 token at a time through the conv cache == full conv."""
    rng = np.random.default_rng(2)
    b, s, c, w = 2, 10, 6, 4
    x = jnp.asarray(rng.standard_normal((b, s, c)), jnp.float32)
    wgt = jnp.asarray(rng.standard_normal((w, c)), jnp.float32)
    bias = jnp.asarray(rng.standard_normal(c), jnp.float32)
    full, _ = _conv1d_causal(x, wgt, bias)
    cache = jnp.zeros((b, w - 1, c))
    outs = []
    for t in range(s):
        o, cache = _conv1d_causal(x[:, t:t + 1], wgt, bias, cache)
        outs.append(o)
    np.testing.assert_allclose(jnp.concatenate(outs, 1), full,
                               rtol=1e-5, atol=1e-5)


def test_encoder_is_bidirectional():
    """Perturbing a FUTURE frame must change an encoder output at position 0
    (and must NOT for a causal LM)."""
    arch = _fp32(get_reduced("hubert-xlarge"))
    model = build_model(arch)
    params = model.init(jax.random.key(0))
    batch = make_batch(arch, 1, 8, seed=0)
    out1, _ = model.forward(params, batch)
    batch2 = dict(batch)
    batch2["frames"] = batch["frames"].at[:, -1].add(1.0)
    out2, _ = model.forward(params, batch2)
    assert float(jnp.max(jnp.abs(out1[:, 0] - out2[:, 0]))) > 1e-6

    arch = _fp32(get_reduced("smollm-360m"))
    model = build_model(arch)
    params = model.init(jax.random.key(0))
    batch = make_batch(arch, 1, 8, seed=0)
    outa, _ = model.forward(params, batch)
    batchb = dict(batch)
    batchb["tokens"] = batch["tokens"].at[:, -1].set(
        (batch["tokens"][:, -1] + 1) % arch.model.vocab_size)
    outb, _ = model.forward(params, batchb)
    np.testing.assert_allclose(outa[:, 0], outb[:, 0], atol=1e-6)


def test_vlm_uses_image():
    arch = _fp32(get_reduced("llama-3.2-vision-90b"))
    model = build_model(arch)
    params = model.init(jax.random.key(0))
    batch = make_batch(arch, 1, 8, seed=0)
    out1, _ = model.forward(params, batch)
    batch2 = dict(batch)
    batch2["image_embeds"] = batch["image_embeds"] * 0.0
    out2, _ = model.forward(params, batch2)
    assert float(jnp.max(jnp.abs(out1 - out2))) > 1e-6


def test_masked_loss_ignores_unmasked():
    arch = _fp32(get_reduced("hubert-xlarge"))
    model = build_model(arch)
    params = model.init(jax.random.key(0))
    batch = make_batch(arch, 2, 8, seed=0)
    loss1, _ = model.loss(params, batch)
    b2 = dict(batch)
    # flip labels outside the mask: loss must not change
    b2["labels"] = jnp.where(batch["mask"], batch["labels"],
                             (batch["labels"] + 7) % arch.model.vocab_size)
    loss2, _ = model.loss(params, b2)
    np.testing.assert_allclose(float(loss1), float(loss2), rtol=1e-6)


def _rel(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("h,m,s,block", [(15, 5, 256, None), (6, 2, 256, None),
                                         (15, 5, 512, 128)],
                         ids=["15-5", "6-2", "15-5-s512-block128"])
def test_flash_sdpa_matches_sdpa(monkeypatch, h, m, s, block, dtype):
    """The splash kernel (interpret mode) against the jnp ``_sdpa`` at a GQA
    shape, head_dim 64, causal: the output and the gradients of q, k and v.
    At s = 256 the kernel runs one 256 block; with 128 blocks at s = 512 it
    runs 4 x 4, so the online softmax across key blocks, the skipping of the
    blocks above the diagonal and the dq/dkv backward over several blocks
    are checked too.  In bf16 both are held to a float32 reference, and the
    kernel (fp32 scores) is at least as close to it as ``_sdpa`` (bf16
    scores)."""
    if block:
        monkeypatch.setattr(attention, "FLASH_BLOCK", block)
    assert attention._flash_block(s) == (block or s)
    b, d = 1, 64
    ks = jax.random.split(jax.random.key(0), 4)
    q = jax.random.normal(ks[0], (b, s, h, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, s, m, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, s, m, d), jnp.float32)
    w = jax.random.normal(ks[3], (b, s, h, d), jnp.float32)

    def flash(q, k, v):
        return attention.flash_sdpa(q, k, v, interpret=True)

    def plain(q, k, v):
        return attention._sdpa(q, k, v, causal=True)

    def out_and_grads(f, dt):
        args = [x.astype(dt) for x in (q, k, v)]
        out, vjp = jax.vjp(f, *args)
        return [out] + list(vjp(w.astype(dt)))

    got = out_and_grads(jax.jit(flash), dtype)
    base = out_and_grads(plain, dtype)
    # the kernel is cached with its mask tables: a second trace reuses them
    again = jax.jit(lambda *a: flash(*a))(*[x.astype(dtype) for x in (q, k, v)])
    np.testing.assert_array_equal(again, got[0])
    if dtype == "float32":
        for a, r in zip(got, base):
            assert _rel(a, r) < 1e-5
        return
    ref = out_and_grads(plain, "float32")
    for a, r, f32 in zip(got, base, ref):
        assert _rel(a, r) < 1e-2
        assert _rel(a, f32) <= 1.05 * _rel(r, f32)


GQA_CASES = ["causal_self", "bidirectional", "cross", "ragged", "model_axis",
             "head_dims"]


@pytest.mark.parametrize("case", GQA_CASES)
def test_attention_takes_kernel_only_where_it_applies(monkeypatch, case):
    """On a TPU, causal GQA self-attention takes the kernel and matches the
    jnp path in output and gradients; a call that fails a precondition
    (bidirectional, cross, a length no kernel block divides, a mesh "model"
    axis over 1, K and V of different head dims as in MLA) never calls the
    kernel and gives the jnp path's result to the bit."""
    cfg = get_reduced("smollm-360m").model.replace(
        dtype="float32", d_model=128, num_heads=6, num_kv_heads=2, head_dim=64)
    s = 200 if case == "ragged" else 256
    kx, kc = jax.random.split(jax.random.key(2))
    x = jax.random.normal(kx, (2, s, cfg.d_model), jnp.float32)
    if case == "model_axis":
        q = k = v = x.reshape(2, s, 2, 64)
        monkeypatch.setattr(attention, "_on_tpu", lambda: True)
        assert attention.flash_ok(q, k, v, causal=True)
        ctx = ShardingCtx(AbstractMesh((1, 2), ("data", "model")), get_reduced(
            "smollm-360m").sharding)
        with use_sharding(ctx):
            assert not attention.flash_ok(q, k, v, causal=True)
        return
    if case == "head_dims":
        p = {"q": x.reshape(2, s, 2, 64), "v": x[..., :64].reshape(2, s, 2, 32)}

        def run(x, p):
            return attention.sdpa(p["q"], p["q"], p["v"], causal=True)
    else:
        p = init_params(jax.random.key(1), attention.gqa_spec(cfg), "float32")
        kw = dict(causal=case != "bidirectional", positions=jnp.arange(s))
        if case == "cross":
            kw.update(kv_src=jax.random.normal(kc, (2, 64, cfg.d_model)),
                      use_rope=False)

        def run(x, p):
            return attention.gqa_attention(x, p, cfg, **kw)

    def out_and_grads():
        out, vjp = jax.vjp(run, x, p)
        return [out] + jax.tree.leaves(vjp(jnp.ones_like(out)))

    parent = out_and_grads()
    calls = []
    kernel = functools.partial(attention.flash_sdpa, interpret=True)
    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    monkeypatch.setattr(attention, "flash_sdpa",
                        lambda *a: calls.append(a) or kernel(*a))
    got = out_and_grads()
    assert bool(calls) == (case == "causal_self")
    for a, r in zip(got, parent):
        if calls:
            assert _rel(a, r) < 1e-5
        else:
            np.testing.assert_array_equal(a, r)
