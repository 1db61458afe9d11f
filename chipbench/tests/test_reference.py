"""Each plain reference against the program at reduced width, on the CPU:
weights, loss, gradients, and prefill plus decode through the cache."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.cell import program_arch
from chipbench.reference import family
from chipbench.reference.common import F32, init_from_layout, weight_key
from chipbench.tests.sizes import tiny_config, tiny_mix

CONFIGS = ["smollm-360m", "mamba2-370m"]


def _program(name, dtype="float32"):
    from repro.models import build_model

    c = tiny_config(name)
    fam = family(c["family"])
    arch = program_arch(c, fam, tiny_mix("train-8x2k"), dtype=dtype)
    return c, fam, build_model(arch)


@pytest.mark.parametrize("name", CONFIGS)
def test_weights_are_the_programs_bit_for_bit(name):
    c, fam, model = _program(name, "bfloat16")
    seed = 2**31 + 5
    prog = model.init(weight_key(seed))
    ref = init_from_layout(weight_key(seed), fam.layout(c), jnp.bfloat16)
    assert jax.tree.structure(prog) == jax.tree.structure(ref)
    assert all(jax.tree.leaves(jax.tree.map(
        lambda a, b: a.shape == b.shape and bool(jnp.all(a == b)), prog, ref)))


@pytest.mark.parametrize("name", CONFIGS)
def test_loss_and_gradients_match_the_program(name):
    c, fam, model = _program(name)
    params = model.init(weight_key(3))
    rng = np.random.default_rng(0)
    tok = jnp.asarray(rng.integers(0, 200, (2, 32)), jnp.int32)
    lab = jnp.asarray(rng.integers(0, 200, (2, 32)), jnp.int32)
    with jax.default_matmul_precision("highest"):
        (pl, _), pg = jax.value_and_grad(model.loss, has_aux=True)(
            params, {"tokens": tok, "labels": lab})
    rl, rg = jax.value_and_grad(
        lambda p: fam.loss_sum(p, tok, lab, c, F32) / tok.size)(params)
    assert abs(float(pl) - float(rl)) < 1e-4
    for a, b in zip(jax.tree.leaves(pg), jax.tree.leaves(rg)):
        scale = float(jnp.max(jnp.abs(b))) + 1e-12
        assert float(jnp.max(jnp.abs(a - b))) / scale < 2e-3


def test_prefill_and_decode_through_the_cache_match_the_reference():
    from repro.serving import Generator

    c, fam, model = _program("smollm-360m")
    params = model.init(weight_key(9))
    prompts = np.random.default_rng(1).integers(0, 200, (2, 5)).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        gen = Generator(model_arch(model), params, max_seq=16)
        logits, cache, pos = gen.prefill(prompts)
        steps = [np.asarray(logits)]
        tok = prompts[:, 0]
        for i in range(3):
            tok = np.asarray(jnp.argmax(steps[-1], -1), np.int32)
            lg, cache = model.decode_step(params, cache, jnp.asarray(tok), jnp.int32(pos + i))
            prompts = np.concatenate([prompts, tok[:, None]], axis=1)
            steps.append(np.asarray(lg))
    ref = np.asarray(fam.logits(params, jnp.asarray(prompts), c, F32))
    for i, lg in enumerate(steps):
        want = ref[:, 4 + i]
        assert np.max(np.abs(lg - want)) / np.max(np.abs(want)) < 1e-4


def model_arch(model):
    from repro.configs import get_arch

    return get_arch("smollm-360m").replace(model=model.cfg)
