"""Served tokens judged by the plain reference: teacher-forced over each
prompt with the tokens served after it, how far each served token's logit
lies below the reference's best."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference.common import F32, Numerics
from chipbench.reference.training import init


def widest_gap(c: dict, fam, seed: int, seqs: np.ndarray, prompt_len: int, *,
               dtype=jnp.bfloat16, control: Numerics | None = None,
               block_rows: int = 4) -> float:
    """``seqs`` (rows, prompt_len + served) int: prompts and what was served.
    Returns the widest gap, over every served token, between the reference's
    largest logit and its logit of the served token.  With ``control`` the
    served token at each position is instead the one that ``control``'s
    numerics, with the weights held as it holds them, put first (the control
    needs no decoding)."""
    held = init(c, fam, seed, dtype)
    params = jax.tree.map(lambda x: x.astype(jnp.float32), held)
    ctrl = None if control is None else jax.tree.map(control.store, held)

    @jax.jit
    def gaps(p, cp, tok, nxt):
        ref = fam.logits(p, tok, c, F32)[:, prompt_len - 1:]
        if cp is not None:
            pick = jnp.argmax(fam.logits(cp, tok, c, control)[:, prompt_len - 1:], -1)
        else:
            pick = nxt
        chosen = jnp.take_along_axis(ref, pick[..., None], axis=-1)[..., 0]
        return jnp.max(jnp.max(ref, axis=-1) - chosen)

    seqs = np.asarray(seqs, np.int32)
    worst = 0.0
    for r in range(0, len(seqs), block_rows):
        blk = seqs[r:r + block_rows]
        worst = max(worst, float(gaps(params, ctrl, jnp.asarray(blk[:, :-1]),
                                      jnp.asarray(blk[:, prompt_len:]))))
    return worst
