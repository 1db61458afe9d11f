"""Step builders: jitted train / prefill / serve steps with explicit shardings.

The communication pattern follows the paper's AllReduce-vs-ScatterReduce
design axis, mapped to TPU-native collectives:

- ``allreduce``      -> pure data parallel: params replicated over "data",
                        gradients all-reduced (the paper's AllReduce, whose
                        leader bottleneck becomes the single all-reduce ring).
- ``scatter_reduce`` -> FSDP via GSPMD: params sharded over "data", grads
                        reduce-scattered + params all-gathered on use (the
                        paper's ScatterReduce: every worker reduces its own
                        partition).
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig, SHAPES, ShapeConfig
from repro.distributed.sharding import ShardingCtx, use_sharding
from repro.models import build_model
from repro.optim import make_optimizer


def _is_axes(x):
    return isinstance(x, tuple) and all(isinstance(a, (str, type(None))) for a in x)


def resolve_shardings(ctx: ShardingCtx, axes_tree, abstract_tree):
    """axes pytree (+ matching abstract tree) -> NamedSharding pytree."""
    return jax.tree.map(
        lambda ax, sds: ctx.param_sharding(sds.shape, ax),
        axes_tree, abstract_tree, is_leaf=_is_axes)


def _value_pspec(ctx: ShardingCtx, shape, axes):
    mesh_axes = [ctx.map.get(a, None) for a in axes]
    mesh_axes = [ctx.fit_axes(shape[i], m) for i, m in enumerate(mesh_axes)]
    return NamedSharding(ctx.mesh, P(*ctx._dedup(mesh_axes)))


def batch_shardings(ctx: ShardingCtx, batch_specs: dict) -> dict:
    axes_by_key = {
        "tokens": ("batch", "seq"),
        "labels": ("batch", "seq"),
        "mask": ("batch", "seq"),
        "frames": ("batch", "seq", "embed"),
        "image_embeds": ("batch", "img_seq", "embed"),
    }
    return {k: _value_pspec(ctx, v.shape, axes_by_key[k])
            for k, v in batch_specs.items()}


@dataclass
class BuiltStep:
    fn: Callable                      # jitted
    in_specs: tuple                   # abstract inputs, positional
    in_shardings: tuple               # their shardings, positional
    ctx: ShardingCtx
    arch: ArchConfig
    kind: str

    def lower(self):
        return self.fn.lower(*self.in_specs)


def _effective_ctx(arch: ArchConfig, mesh: Mesh, kind: str = "train",
                   global_batch: int | None = None) -> ShardingCtx:
    rules = arch.sharding
    if arch.train.comm_pattern == "allreduce":
        rules = dataclasses.replace(rules, fsdp_axis=None)
    if rules.dp_over_model:
        n_dp = 1
        for a in mesh.axis_names:
            n_dp *= mesh.shape[a]
        if kind != "train" or (global_batch is not None
                               and global_batch % n_dp != 0):
            # pure DP needs batch % (all mesh axes) == 0; inference batches
            # (32/128/1) and multi-pod 256-batch train don't divide -- keep
            # the arch's TP layout instead
            rules = dataclasses.replace(rules, dp_over_model=False)
    return ShardingCtx(mesh, rules)


# ------------------------------------------------------------- train ---------

def build_train_step(arch: ArchConfig, mesh: Mesh, shape: ShapeConfig | str,
                     batch_specs: dict | None = None) -> BuiltStep:
    sh = SHAPES[shape] if isinstance(shape, str) else shape
    model = build_model(arch)
    tc = arch.train
    opt = make_optimizer(tc)
    ctx = _effective_ctx(arch, mesh, "train", sh.global_batch)

    params_abs = model.abstract()
    param_sh = resolve_shardings(ctx, model.axes(), params_abs)
    opt_abs = jax.eval_shape(opt.init, params_abs)
    opt_sh = resolve_shardings(ctx, opt.state_axes(model.axes()), opt_abs)

    if batch_specs is None:
        from repro.launch.specs import input_specs
        batch_specs = input_specs(arch, sh)["batch"]
    batch_sh = batch_shardings(ctx, batch_specs)

    def loss_of(p, b):
        return model.loss(p, b, remat=tc.remat, scan_layers=tc.scan_layers)

    def train_step(params, opt_state, batch):
        with use_sharding(ctx):
            k = tc.micro_batches
            if k > 1:
                mb = jax.tree.map(
                    lambda x: x.reshape((k, x.shape[0] // k) + x.shape[1:]), batch)
                acc0 = jax.tree.map(
                    lambda p: jnp.zeros(p.shape, jnp.float32), params)

                def micro(acc, b):
                    (l, m), g = jax.value_and_grad(loss_of, has_aux=True)(params, b)
                    return jax.tree.map(lambda a, gg: a + gg.astype(jnp.float32),
                                        acc, g), (l, m)
                grads, (ls, ms) = jax.lax.scan(micro, acc0, mb)
                grads = jax.tree.map(lambda g: g / k, grads)
                metrics = jax.tree.map(jnp.mean, ms)
            else:
                (_, metrics), grads = jax.value_and_grad(
                    loss_of, has_aux=True)(params, batch)
            with jax.named_scope("optimizer"):
                new_p, new_s, stats = opt.update(grads, opt_state, params)
        metrics = dict(metrics)
        metrics.update(stats)
        return new_p, new_s, metrics

    fn = jax.jit(
        train_step,
        in_shardings=(param_sh, opt_sh, batch_sh),
        out_shardings=(param_sh, opt_sh, None),
        donate_argnums=(0, 1),
    )
    return BuiltStep(fn, (params_abs, opt_abs, batch_specs),
                     (param_sh, opt_sh, batch_sh), ctx, arch, "train")


# ------------------------------------------------------------ prefill --------

def build_prefill_step(arch: ArchConfig, mesh: Mesh, shape: ShapeConfig | str,
                       batch_specs: dict | None = None) -> BuiltStep:
    sh = SHAPES[shape] if isinstance(shape, str) else shape
    model = build_model(arch)
    ctx = _effective_ctx(arch, mesh, "prefill")
    params_abs = model.abstract()
    param_sh = resolve_shardings(ctx, model.axes(), params_abs)
    if batch_specs is None:
        from repro.launch.specs import input_specs
        batch_specs = input_specs(arch, sh)["batch"]
    batch_sh = batch_shardings(ctx, batch_specs)

    def prefill_step(params, batch):
        with use_sharding(ctx):
            logits, _ = model.forward(params, batch, last_only=True,
                                      scan_layers=arch.train.scan_layers)
        return logits

    fn = jax.jit(prefill_step, in_shardings=(param_sh, batch_sh),
                 out_shardings=None)
    return BuiltStep(fn, (params_abs, batch_specs), (param_sh, batch_sh), ctx,
                     arch, "prefill")


# ------------------------------------------------------------- serve ---------

def build_serve_step(arch: ArchConfig, mesh: Mesh, shape: ShapeConfig | str) -> BuiltStep:
    sh = SHAPES[shape] if isinstance(shape, str) else shape
    model = build_model(arch)
    ctx = _effective_ctx(arch, mesh, "decode")
    params_abs = model.abstract()
    param_sh = resolve_shardings(ctx, model.axes(), params_abs)
    cache_abs = model.init_cache(sh.global_batch, sh.seq_len, abstract=True)
    cache_sh = resolve_shardings(ctx, model.cache_axes(), cache_abs)
    tok_abs = jax.ShapeDtypeStruct((sh.global_batch,), jnp.int32)
    tok_sh = _value_pspec(ctx, tok_abs.shape, ("batch",))
    pos_abs = jax.ShapeDtypeStruct((), jnp.int32)
    pos_sh = NamedSharding(mesh, P())

    def serve_step(params, cache, token, pos):
        with use_sharding(ctx):
            logits, cache = model.decode_step(params, cache, token, pos)
        return logits, cache

    fn = jax.jit(serve_step,
                 in_shardings=(param_sh, cache_sh, tok_sh, pos_sh),
                 out_shardings=(None, cache_sh),
                 donate_argnums=(1,))
    return BuiltStep(fn, (params_abs, cache_abs, tok_abs, pos_abs),
                     (param_sh, cache_sh, tok_sh, pos_sh), ctx, arch, "decode")


def build_step(arch: ArchConfig, mesh: Mesh, shape: ShapeConfig | str) -> BuiltStep:
    sh = SHAPES[shape] if isinstance(shape, str) else shape
    if sh.kind == "train":
        return build_train_step(arch, mesh, sh)
    if sh.kind == "prefill":
        return build_prefill_step(arch, mesh, sh)
    return build_serve_step(arch, mesh, sh)
