"""The first training steps of a configuration, by the plain reference:
weights drawn from the seed as the layout states, the family's float32 loss
and its gradients accumulated over blocks of rows, AdamW as the job states."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from chipbench.reference.common import F32, Numerics, adamw, init_from_layout, leaf_norms, weight_key


def init(c: dict, fam, seed: int, dtype=jnp.bfloat16, sharding=None):
    """The weights of ``seed`` in the configuration's dtype, in one call."""
    lay = fam.layout(c)
    return jax.jit(lambda k: init_from_layout(k, lay, dtype),
                   out_shardings=sharding)(weight_key(seed))


def train_steps(c: dict, fam, seed: int, batches: list, opt: dict, *,
                num: Numerics = F32, block_rows: int = 1, devices=None,
                fault: str | None = None) -> dict:
    """Run ``len(batches)`` steps from the weights of ``seed``, held and
    computed as ``num`` says.  Returns the
    loss of each step, the norm of each leaf of the first step's gradient as
    the optimizer takes it (after clipping), and the norm of each leaf's
    change over all the steps.  Rows are spread over ``devices``.

    ``fault`` plants a fault in this reference, for reading what the
    comparison makes of it: ``"half_batch"`` leaves out the second half of
    every batch and takes the mean over the rest."""
    devices = list(devices or jax.devices()[:1])
    mesh = Mesh(np.array(devices), ("rows",))
    rep = NamedSharding(mesh, P())
    rows_sh = NamedSharding(mesh, P("rows"))
    params = jax.jit(lambda p: jax.tree.map(num.store, p), out_shardings=rep)(
        init(c, fam, seed, sharding=rep))
    p0 = params

    def loss_sum(p, tok, lab):
        p32 = jax.tree.map(lambda x: x.astype(jnp.float32), p)
        return fam.loss_sum(p32, tok, lab, c, num)

    @jax.jit
    def accumulate(acc, total, p, tok, lab):
        loss, g = jax.value_and_grad(loss_sum)(p, tok, lab)
        return jax.tree.map(jnp.add, acc, g), total + loss

    @jax.jit
    def update(p, acc, total, m, v, t, denom):
        grads = jax.tree.map(lambda a: a / denom, acc)
        p, m, v, clipped = adamw(p, grads, m, v, t, lr=opt["lr"],
                                 beta1=opt["beta1"], beta2=opt["beta2"],
                                 weight_decay=opt["weight_decay"],
                                 grad_clip=opt["grad_clip"], store=num.store)
        return p, m, v, total / denom, leaf_norms(clipped)

    zeros = jax.jit(lambda p: jax.tree.map(
        lambda x: jnp.zeros(x.shape, jnp.float32), p), out_shardings=rep)
    m, v = zeros(params), zeros(params)
    losses, grad1 = [], None
    per_call = block_rows * len(devices)
    for t, batch in enumerate(batches, start=1):
        tok, lab = batch["tokens"], batch["labels"]
        if fault == "half_batch":
            tok, lab = tok[: len(tok) // 2], lab[: len(lab) // 2]
        acc, total = zeros(params), jnp.zeros((), jnp.float32)
        for r in range(0, len(tok), per_call):
            acc, total = accumulate(
                acc, total, params,
                jax.device_put(tok[r:r + per_call], rows_sh),
                jax.device_put(lab[r:r + per_call], rows_sh))
        params, m, v, loss, norms = update(params, acc, total, m, v,
                                           jnp.float32(t), jnp.float32(tok.size))
        del acc
        losses.append(float(loss))
        if grad1 is None:
            grad1 = {k: float(x) for k, x in norms.items()}
    change = jax.jit(lambda a, b: leaf_norms(jax.tree.map(
        lambda x, y: x.astype(jnp.float32) - y.astype(jnp.float32), a, b)))(params, p0)
    return {"losses": losses, "grad1": grad1,
            "change": {k: float(x) for k, x in change.items()}}
