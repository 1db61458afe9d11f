"""Distributed training launcher: compose mesh + steps + data + checkpoints.

    PYTHONPATH=src python -m repro.launch.train --arch smollm-360m --reduced \
        --steps 50 --batch 8 --seq 128 --mesh 1x1

Without --mesh the launcher runs pure data parallelism over every device of
the host: a ("data","model") mesh with data = device count, model = 1.  A
mesh with a leading "pod" axis (e.g. --mesh 2x2x2) plus --algorithm
ma_sgd|diloco runs the cross-pod-efficient MA-SGD path.  Fault tolerance:
deadline-aware checkpointing via PreemptionGuard; rerun the same command to
resume (elastic: change --data-workers between runs).

``train()`` is the same loop as a function, for callers that drive it in
their own process (``chip_smoke.py``).
"""
from __future__ import annotations

import argparse
import dataclasses
import time
from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro import checkpoint as ckpt
from repro.configs import get_arch, get_reduced
from repro.configs.base import ArchConfig, ShapeConfig
from repro.data.tokens import TokenStream
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_data_mesh, make_mesh


def _mesh_from_arg(arg: str | None):
    if arg:
        dims = tuple(int(x) for x in arg.split("x"))
        names = (("data", "model") if len(dims) == 2
                 else ("pod", "data", "model"))
        return make_mesh(dims, names)
    return make_data_mesh()


@dataclass
class TrainRun:
    """What ``train`` returns: one entry per step it ran."""
    losses: list[float]
    step_s: list[float]   # host clock per step, ending in block_until_ready
    params: Any           # final parameters (pod-stacked under local SGD)
    batch: dict           # the last batch, as placed for the step


def train(arch: ArchConfig, mesh, *, steps: int, batch_size: int, seq: int,
          ckpt_dir: str | None = None, ckpt_every: int = 50,
          lifetime: float = 900.0, data_worker: int = 0,
          data_workers: int = 1, log_every: int = 10) -> TrainRun:
    """Run ``steps`` train steps of ``arch`` on ``mesh`` over
    ``TokenStream(seed=0)`` batches, resuming from ``ckpt_dir`` if it holds
    a checkpoint."""
    tc = arch.train
    shape = ShapeConfig("cli", seq, batch_size, "train")
    local_sgd = (tc.algorithm in ("ma_sgd", "diloco")
                 and "pod" in mesh.axis_names)

    from repro.models import build_model
    from repro.optim import make_optimizer
    model = build_model(arch)
    opt = make_optimizer(tc)
    stream = TokenStream(arch.model.vocab_size, seed=0, worker=data_worker,
                         num_workers=data_workers)

    print(f"arch={arch.name} ({model.param_count():,} params) "
          f"mesh={dict(zip(mesh.axis_names, mesh.devices.shape))} "
          f"algo={tc.algorithm} local_sgd={local_sgd}")

    with mesh:
        if local_sgd:
            from repro.distributed.local_sgd import build_local_sgd
            ls = build_local_sgd(arch, mesh, shape)
            P = ls.n_pods
            params = model.init(jax.random.key(0))
            params_st = jax.tree.map(lambda x: jnp.stack([x] * P), params)
            opt_st = jax.tree.map(lambda x: jnp.stack([x] * P),
                                  opt.init(params))
            outer = ls.init_outer_fn(params_st)
            batch_sh = None
        else:
            from repro.distributed.step import build_train_step
            from repro.launch.specs import input_specs
            specs = {
                k: jax.ShapeDtypeStruct((batch_size,) + v.shape[1:], v.dtype)
                for k, v in input_specs(arch, shape)["batch"].items()}
            step = build_train_step(arch, mesh, shape, batch_specs=specs)
            param_sh, opt_sh, batch_sh = step.in_shardings
            params = jax.jit(model.init, out_shardings=param_sh)(
                jax.random.key(0))
            opt_state = jax.jit(opt.init, out_shardings=opt_sh)(params)

        # resume
        step0 = 0
        if ckpt_dir:
            restored, meta = ckpt.load_latest(ckpt_dir)
            if restored is not None:
                step0 = int(meta["step"])
                stream.restore(meta["stream"], data_worker, data_workers)
                if local_sgd:
                    params_st = jax.tree.map(jnp.asarray, restored["params"])
                    opt_st = jax.tree.map(jnp.asarray, restored["opt"])
                else:
                    params = jax.device_put(restored["params"], param_sh)
                    opt_state = jax.device_put(restored["opt"], opt_sh)
                print(f"resumed from step {step0}")

        guard = ckpt.PreemptionGuard(lifetime_s=lifetime)
        t0 = time.time()
        losses, step_s = [], []
        b = None
        for it in range(step0, steps):
            b = jax.device_put(stream.batch(batch_size, seq), batch_sh)
            ts = time.perf_counter()
            if local_sgd:
                params_st, opt_st, m = ls.inner_fn(params_st, opt_st, b)
                if (it + 1) % ls.sync_period == 0:
                    params_st, outer = ls.outer_fn(params_st, outer)
                jax.block_until_ready((params_st, opt_st, m))
            else:
                params, opt_state, m = step.fn(params, opt_state, b)
                jax.block_until_ready((params, opt_state, m))
            dt = time.perf_counter() - ts
            loss = float(np.asarray(m["loss"]).mean())
            losses.append(loss)
            step_s.append(dt)
            guard.record_step(dt)
            if it % log_every == 0 or it == steps - 1:
                print(f"step {it:5d}  loss {loss:.4f}  "
                      f"{time.time() - t0:6.1f}s")
            if ckpt_dir and ((it and it % ckpt_every == 0)
                             or guard.should_checkpoint()):
                tree = ({"params": params_st, "opt": opt_st} if local_sgd
                        else {"params": params, "opt": opt_state})
                ckpt.save(ckpt_dir, it + 1, tree, {"stream": stream.state()})
                ckpt.retain(ckpt_dir, keep=2)
                if guard.should_checkpoint():
                    print(f"step {it}: lifetime deadline -- checkpointed; "
                          "re-invoke to resume")
                    guard.renew()
        if ckpt_dir:
            tree = ({"params": params_st, "opt": opt_st} if local_sgd
                    else {"params": params, "opt": opt_state})
            ckpt.save(ckpt_dir, steps, tree, {"stream": stream.state()})
    return TrainRun(losses, step_s, params_st if local_sgd else params, b)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-360m")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8, help="global batch")
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--mesh", default=None,
                    help="e.g. 1x1, 2x4, 2x2x2 (default: data over all "
                         "devices)")
    ap.add_argument("--algorithm", default=None,
                    choices=[None, "ga_sgd", "ma_sgd", "diloco"])
    ap.add_argument("--sync-period", type=int, default=None)
    ap.add_argument("--compress", action="store_true")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--lifetime", type=float, default=900.0)
    ap.add_argument("--data-workers", type=int, default=1)
    ap.add_argument("--data-worker", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args()
    enable_compile_cache()

    arch = get_reduced(args.arch) if args.reduced else get_arch(args.arch)
    tc = arch.train
    if args.algorithm:
        tc = dataclasses.replace(tc, algorithm=args.algorithm)
    if args.sync_period:
        tc = dataclasses.replace(tc, sync_period=args.sync_period)
    if args.compress:
        tc = dataclasses.replace(tc, compress_cross_pod=True)
    # micro-batching needs batch % micro == 0 on arbitrary CLI batches
    if args.batch % max(tc.micro_batches, 1) != 0:
        tc = dataclasses.replace(tc, micro_batches=1)
    arch = arch.replace(train=tc)

    run = train(arch, _mesh_from_arg(args.mesh), steps=args.steps,
                batch_size=args.batch, seq=args.seq, ckpt_dir=args.ckpt_dir,
                ckpt_every=args.ckpt_every, lifetime=args.lifetime,
                data_worker=args.data_worker, data_workers=args.data_workers,
                log_every=args.log_every)
    loss = run.losses[-1] if run.losses else float("nan")
    print(f"done: step {args.steps}, loss {loss:.4f}")


if __name__ == "__main__":
    main()
