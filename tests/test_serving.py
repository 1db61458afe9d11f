"""Serving: greedy generation consistency + perplexity sanity."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import serving
from repro.configs import get_reduced
from repro.models import build_model
from repro.serving import Generator, perplexity


@pytest.fixture(scope="module")
def small():
    arch = get_reduced("smollm-360m")
    arch = arch.replace(model=arch.model.replace(dtype="float32"))
    model = build_model(arch)
    params = model.init(jax.random.key(0))
    return arch, model, params


def test_greedy_generation_matches_forward_argmax(small):
    """The first generated token must equal argmax of the forward logits at
    the last prompt position (teacher forcing <-> decode equivalence)."""
    arch, model, params = small
    rng = np.random.default_rng(0)
    prompts = rng.integers(0, arch.model.vocab_size, (2, 7)).astype(np.int32)
    gen = Generator(arch, params, max_seq=32)
    out = gen.generate(prompts, max_new_tokens=3)
    assert out.shape == (2, 10)
    logits, _ = model.forward(params, {"tokens": jnp.asarray(prompts)})
    want = np.asarray(jnp.argmax(logits[:, -1], axis=-1))
    np.testing.assert_array_equal(out[:, 7], want)


def test_generation_deterministic(small):
    arch, _, params = small
    rng = np.random.default_rng(1)
    prompts = rng.integers(0, arch.model.vocab_size, (1, 5)).astype(np.int32)
    gen = Generator(arch, params, max_seq=16)
    a = gen.generate(prompts, max_new_tokens=4)
    b = gen.generate(prompts, max_new_tokens=4)
    np.testing.assert_array_equal(a, b)


def test_sampling_temperature(small):
    arch, _, params = small
    rng = np.random.default_rng(2)
    prompts = rng.integers(0, arch.model.vocab_size, (1, 4)).astype(np.int32)
    gen = Generator(arch, params, max_seq=16)
    a = gen.generate(prompts, max_new_tokens=6, temperature=2.0, seed=1)
    b = gen.generate(prompts, max_new_tokens=6, temperature=2.0, seed=2)
    assert a.shape == b.shape == (1, 10)
    # different seeds should (overwhelmingly) differ at high temperature
    assert not np.array_equal(a, b)


def test_perplexity_finite(small):
    arch, model, params = small
    rng = np.random.default_rng(3)
    toks = rng.integers(0, arch.model.vocab_size, (2, 16)).astype(np.int32)
    p = perplexity(model, params, toks)
    assert np.isfinite(p) and p > 1.0


# ------------------------------------------------------- chunked prefill ----

C = 8   # PREFILL_CHUNK in these tests: a small C with a small cache


@pytest.fixture(scope="module", params=[32, 20], ids=["cache32", "cache20"])
def chunked(request, small):
    """A Generator whose prefill feeds chunks of C; one cache length a
    multiple of C, one not (a 2C+3 prompt then shifts its last chunk back)."""
    arch, model, params = small
    mp = pytest.MonkeyPatch()
    mp.setattr(serving, "PREFILL_CHUNK", C)
    yield model, params, Generator(arch, params, max_seq=request.param)
    mp.undo()


def _token_loop(model, params, prompts, max_seq, new_tokens=0):
    """The token-by-token reference: decode_step at every prompt position,
    then greedy decode -> (logits after the prompt, cache, tokens)."""
    step = jax.jit(model.decode_step)
    b, s = prompts.shape
    cache = model.init_cache(b, max_seq)
    for pos in range(s):
        logits, cache = step(params, cache, jnp.asarray(prompts[:, pos]),
                             jnp.int32(pos))
    first, out, lg = logits, [prompts], logits
    for i in range(new_tokens):
        tok = jnp.argmax(lg, axis=-1).astype(jnp.int32)
        out.append(np.asarray(tok)[:, None])
        lg, cache = step(params, cache, tok, jnp.int32(s + i))
    return first, cache, np.concatenate(out, axis=1)


@pytest.mark.parametrize("s", [1, C - 1, C, C + 1, 2 * C + 3])
def test_chunked_prefill_matches_token_loop(chunked, s):
    model, params, gen = chunked
    prompts = np.random.default_rng(s).integers(
        0, model.cfg.vocab_size, (2, s)).astype(np.int32)
    before = gen.prefill_chunks
    logits, cache, pos = gen.prefill(prompts)
    want, ref_cache, _ = _token_loop(model, params, prompts, gen.max_seq)
    assert pos == s and gen.prefill_chunks - before == -(-s // C)
    np.testing.assert_allclose(logits, want, rtol=1e-5,
                               atol=1e-5 * float(jnp.max(jnp.abs(want))))
    for k in ("k", "v"):
        got, ref = cache[k][:, :, :s], ref_cache[k][:, :, :s]
        np.testing.assert_allclose(got, ref, rtol=1e-5,
                                   atol=1e-5 * float(jnp.max(jnp.abs(ref))))
    assert gen.prefill_fn._cache_size() == 1      # one compile, every length


def test_chunked_generate_matches_token_loop(chunked):
    model, params, gen = chunked
    prompts = np.random.default_rng(7).integers(
        0, model.cfg.vocab_size, (2, gen.max_seq - 8)).astype(np.int32)
    _, _, want = _token_loop(model, params, prompts, gen.max_seq, new_tokens=8)
    before = gen.decode_steps
    np.testing.assert_array_equal(gen.generate(prompts, max_new_tokens=8), want)
    assert gen.decode_steps - before == 8
