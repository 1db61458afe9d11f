"""Training cells: the program's own train step (``build_train_step`` on a
data mesh), driven as ``repro.launch.train.train`` drives it per step, on
batches from the traffic mix.

Set-up builds the step and its state once, drives it from the seed through
its first steps by the window's own call and feed, and keeps what the
comparison needs: each step's loss, the first gradient as the optimizer got
it (read from its first moment after one step) and each leaf's change over
those steps.  The window then runs the same object.  Once it has closed and
the state is freed, the plain reference runs the same steps."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import compare, generator
from chipbench.cell import Context, memory_peak, now, program_arch, span, traced
from chipbench.reference import training as ref_training
from chipbench.reference.common import leaf_norms, weight_key
from chipbench.trace import find_xplane, reduce_trace

SPANS = ("batch transfer", "step dispatch", "wait", "loss fetch")


def run(ctx: Context) -> dict:
    from repro.configs.base import ShapeConfig
    from repro.distributed.step import build_train_step
    from repro.launch.mesh import make_data_mesh
    from repro.models import build_model
    from repro.optim import make_optimizer

    rec, mix, c = ctx.rec, ctx.rec.mix, ctx.rec.config
    fam = rec.family
    B, S = mix["global_batch"], mix["seq_len"]
    arch = program_arch(c, fam, mix)
    vocab = arch.model.vocab_size
    mesh = make_data_mesh(ctx.devices)
    specs = {k: jax.ShapeDtypeStruct((B, S), jnp.int32) for k in ("tokens", "labels")}
    step = build_train_step(arch, mesh, ShapeConfig("chipbench", S, B, "train"),
                            batch_specs=specs)
    param_sh, opt_sh, batch_sh = step.in_shardings
    model, opt = build_model(arch), make_optimizer(arch.train)
    batches = [generator.train_batch(mix, vocab, ctx.seed, i) for i in range(mix["pool"])]
    feed = batches
    if ctx.fault == "half_batch":
        feed = [{k: np.concatenate([v[: B // 2], v[: B - B // 2]]) for k, v in b.items()}
                for b in batches]
    fn = step.fn
    if ctx.fault == "unchanged":
        fn = jax.jit(lambda p, o, b: (p, o, model.loss(p, b)[1]))

    with mesh:
        st = {"params": jax.jit(model.init, out_shardings=param_sh)(weight_key(ctx.seed))}
        st["opt"] = jax.jit(opt.init, out_shardings=opt_sh)(st["params"])
        p0 = jax.jit(lambda p: jax.tree.map(jnp.copy, p), out_shardings=param_sh)(st["params"])

        def one_step(i: int) -> float:
            with span("batch transfer"):
                b = jax.device_put(feed[i % len(feed)], batch_sh)
            with span("step dispatch"):
                st["params"], st["opt"], m = fn(st["params"], st["opt"], b)
            with span("wait"):
                jax.block_until_ready((st["params"], st["opt"], m))
            with span("loss fetch"):
                return float(m["loss"])

        beta1 = arch.train.beta1
        first_grad = jax.jit(lambda m: leaf_norms(jax.tree.map(lambda x: x / (1 - beta1), m)))
        change = jax.jit(lambda a, b: leaf_norms(jax.tree.map(
            lambda x, y: x.astype(jnp.float32) - y.astype(jnp.float32), a, b)))
        n_check = mix["check_steps"]
        prog = {"losses": []}
        for i in range(n_check):
            prog["losses"].append(one_step(i))
            if i == 0:
                prog["grad1"] = _floats(first_grad(st["opt"]["m"]))
        prog["change"] = _floats(change(st["params"], p0))
        del p0
        setup_s = now() - ctx.t_start

        i, losses = n_check, []
        if ctx.trace:
            with traced(rec.workload) as tdir:
                with span("window"):
                    t0 = now()
                    for _ in range(mix["trace_steps"]):
                        losses.append(one_step(i))
                        i += 1
                    wall = now() - t0
            rec.trace = reduce_trace(find_xplane(tdir), SPANS)
            rec.traced = {"steps": mix["trace_steps"], "rows": B, "seq": S, "wall_s": wall}
        else:
            t0 = now()
            while True:
                losses.append(one_step(i))
                i += 1
                if now() - t0 >= ctx.seconds:
                    break
            rec.window_s = now() - t0
            rec.work_tokens = len(losses) * B * S
        rec.setup_s = setup_s
        peak = memory_peak(ctx.devices)
        placed = {k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=batch_sh[k])
                  for k, v in specs.items()}
        ma = step.fn.lower(st["params"], st["opt"], placed).compile().memory_analysis()
        ctx.log(f"memory_analysis per device: arguments {ma.argument_size_in_bytes} "
                f"temporaries {ma.temp_size_in_bytes} outputs {ma.output_size_in_bytes} "
                f"aliased {ma.alias_size_in_bytes}; peak_bytes_in_use {peak}")
        st.clear()

    ref = ref_training.train_steps(c, fam, ctx.seed, batches[:n_check], mix["optimizer"],
                                   block_rows=mix["reference_rows"], devices=ctx.devices)
    numbers = compare.train_numbers(prog, ref)
    ctx.log(f"losses of the first steps: program {prog['losses']} reference {ref['losses']}")
    finite = all(np.isfinite(losses))
    return {"numbers": numbers, "attempted": len(losses),
            "failed": int(np.sum(~np.isfinite(losses))), "finite": finite,
            "memory_peak_bytes": peak, "prog": prog, "ref": ref}


def _floats(tree: dict) -> dict:
    return {k: float(v) for k, v in tree.items()}

