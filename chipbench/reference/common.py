"""Numerics shared by the family references: weights from the seed, matrix
products at a stated precision, norms, losses and AdamW."""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


def weight_key(seed: int) -> jax.Array:
    """The JAX key the weights of ``--seed`` are drawn from: any whole number
    is hashed to 32 bits, since a JAX seed keeps only its low 32 bits."""
    word = np.random.SeedSequence([int(seed), 0x5EED]).generate_state(1)[0]
    return jax.random.key(int(word))


@dataclass(frozen=True)
class Numerics:
    """How the reference holds its weights and computes its matrix products.

    ``f32`` (the reference): weights held in bfloat16, as the configurations
    state, and every product of float32 operands at the highest precision.
    ``fp8`` (the control, one precision below the bfloat16 the configurations
    state): weights held in float8 e4m3 with one scale per tensor, and the
    operands of every product rounded to it, accumulated in float32."""
    kind: str = "f32"

    def __post_init__(self):
        if self.kind not in ("f32", "fp8"):
            raise ValueError(self.kind)

    @staticmethod
    def _fp8(x):
        scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
        return (x / scale).astype(F8).astype(jnp.float32) * scale

    def cast(self, x):
        """A product's operand; the rounding passes gradients straight through."""
        x = x.astype(jnp.float32)
        if self.kind == "f32":
            return x
        return x + jax.lax.stop_gradient(self._fp8(jax.lax.stop_gradient(x)) - x)

    def store(self, x):
        """A weight as it is held."""
        if self.kind == "f32":
            return x.astype(jnp.bfloat16)
        return self._fp8(x.astype(jnp.float32))

    def mm(self, eq: str, a, b):
        return jnp.einsum(eq, self.cast(a), self.cast(b), precision=HIGHEST,
                          preferred_element_type=jnp.float32)


F32 = Numerics("f32")
FP8 = Numerics("fp8")


def init_from_layout(key, layout: dict, dtype) -> dict:
    """Weights drawn as the layout states.  ``layout`` is a nested dict whose
    leaves are ``(shape, scale)``; leaves are taken in sorted-key order, each
    from its own split of ``key``.  A scale of 0 gives zeros, a scale of 1 on
    a vector gives ones, any other leaf a normal truncated at two standard
    deviations times its scale.  Returned in ``dtype``."""
    leaves, treedef = jax.tree.flatten(layout, is_leaf=_is_layout_leaf)
    keys = jax.random.split(key, len(leaves))
    out = []
    for k, (shape, scale) in zip(keys, leaves):
        if scale == 0.0:
            out.append(jnp.zeros(shape, dtype))
        elif scale == 1.0 and len(shape) == 1:
            out.append(jnp.ones(shape, dtype))
        else:
            out.append((jax.random.truncated_normal(k, -2, 2, shape, jnp.float32)
                        * scale).astype(dtype))
    return jax.tree.unflatten(treedef, out)


def _is_layout_leaf(x) -> bool:
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[0], tuple)


def rms_norm(x, scale, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def silu(x):
    return x * jax.nn.sigmoid(x)


def nll_sum(logits, labels):
    """Sum over tokens of -log softmax(logits)[label], in float32."""
    logits = logits.astype(jnp.float32)
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.sum(logz - picked)


def adamw(params, grads, m, v, t, *, lr, beta1, beta2, weight_decay,
          grad_clip, store, eps=1e-8):
    """One AdamW step with clipping by the global norm, in float32; each new
    weight is held as ``store`` holds it.  Returns (params, m, v, clipped
    gradients)."""
    gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, grad_clip / jnp.maximum(gnorm, 1e-9))
    grads = jax.tree.map(lambda g: g * scale, grads)
    m = jax.tree.map(lambda m_, g: beta1 * m_ + (1 - beta1) * g, m, grads)
    v = jax.tree.map(lambda v_, g: beta2 * v_ + (1 - beta2) * g * g, v, grads)
    c1, c2 = 1 - beta1 ** t, 1 - beta2 ** t

    def step(p, m_, v_):
        p32 = p.astype(jnp.float32)
        u = (m_ / c1) / (jnp.sqrt(v_ / c2) + eps) + weight_decay * p32
        return store(p32 - lr * u)

    return jax.tree.map(step, params, m, v), m, v, grads


def leaf_norms(tree) -> dict:
    """{path: float32 norm} of every leaf, paths joined with '/'."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {"/".join(str(getattr(k, "key", k)) for k in path):
            jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
            for path, x in flat}
