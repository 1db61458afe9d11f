"""Smoke run of the main paths on a TPU: train, serve and the codec kernels.

    python chip_smoke.py               # one chip: the three phases below
    python chip_smoke.py --four-chips  # a v5e 2x2 host: data-parallel train

Full published width of smollm-360m (32 layers, d_model 960, vocab 49152)
with random weights and data drawn from seeds, through the launchers' own
functions (``repro.launch.train.train``, ``repro.launch.serve.serve``) in
this one process.  It refuses to run where JAX finds no TPU; it never falls
back to the CPU.  Every phase checks its outputs and any failure exits
non-zero.  The times it prints are smoke numbers from one run, not
benchmark numbers.  The last line of standard output is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import statistics
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_arch  # noqa: E402
from repro.configs.base import ArchConfig  # noqa: E402
from repro.kernels.quant8.ops import int8_roundtrip  # noqa: E402
from repro.kernels.topk_ef.ops import topk_ef  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_data_mesh  # noqa: E402
from repro.launch.serve import build_generator, serve  # noqa: E402
from repro.launch.train import train  # noqa: E402
from repro.models import build_model  # noqa: E402

ARCH = "smollm-360m"
TRAIN_BATCH, TRAIN_SEQ, TRAIN_STEPS, FOUR_CHIP_STEPS = 8, 2048, 5, 3
SERVE_REQUESTS, SERVE_BATCH, PROMPT_LEN, NEW_TOKENS = 2, 4, 32, 32
TOPK_FRACTION = 0.01
#: step-0 loss of random weights sits near ln(vocab): uniform predictions
STEP0_LOSS_BAND = 1.5
#: data-parallel vs one-device losses: the loss is an fp32 mean over bf16
#: logits, and the gradients are summed in another order across chips; one
#: bf16 ulp of a loss in [8, 16) is 2**-4 = 0.0625, so 0.05 is under one ulp
LOSS_TOL_BF16 = 0.05
#: prefill logits vs the training forward: on the TPU a float32 matmul at
#: default precision rounds its operands to bf16 (unit roundoff 2**-8), and
#: the two paths round differently ordered sums; over 32 layers the error
#: stays a few roundoffs of the logit scale, so 2**-5 of max|logit|
SERVE_LOGIT_TOL = 2.0 ** -5


class SmokeFailure(Exception):
    pass


def _check(cond: bool, msg: str):
    if not cond:
        raise SmokeFailure(msg)


def device_info(devices) -> dict:
    """The device as JAX reports it; refuses anything but a TPU."""
    d = devices[0]
    _check(d.platform == "tpu",
           f"JAX found no TPU (platform {d.platform!r}); this smoke run "
           "needs the chip")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def check_codec_backend(env=os.environ):
    backend = env.get("REPRO_CODEC_BACKEND", "kernel")
    _check(backend == "kernel",
           f"REPRO_CODEC_BACKEND={backend!r} would bypass the kernels")


# ------------------------------------------------------------------ train --

def train_phase(arch: ArchConfig, device, *, batch: int, seq: int,
                steps: int) -> dict:
    run = train(arch, make_data_mesh([device]), steps=steps,
                batch_size=batch, seq=seq, log_every=1)
    losses = run.losses
    _check(all(math.isfinite(x) for x in losses), f"non-finite loss {losses}")
    ln_v = math.log(arch.model.vocab_size)
    _check(abs(losses[0] - ln_v) <= STEP0_LOSS_BAND,
           f"step-0 loss {losses[0]} not within {STEP0_LOSS_BAND} of "
           f"ln(vocab) {ln_v}")
    _check(losses[-1] < losses[0], f"loss did not fall: {losses}")
    steady = statistics.median(run.step_s[1:])
    stats = device.memory_stats() or {}
    return {"losses": losses, "step_s": run.step_s, "steady_step_s": steady,
            "tokens_per_s": batch * seq / steady,
            "compile_s": run.step_s[0] - steady,
            "peak_bytes_in_use": stats.get("peak_bytes_in_use")}


# ------------------------------------------------------------------ serve --

def serve_phase(arch: ArchConfig, *, requests: int, batch: int,
                prompt_len: int, new_tokens: int) -> dict:
    gen = build_generator(arch, max_seq=prompt_len + new_tokens + 1)
    run = serve(gen, requests=requests, batch=batch, prompt_len=prompt_len,
                new_tokens=new_tokens)
    vocab = arch.model.vocab_size
    fwd = jax.jit(lambda p, t: gen.model.forward(
        p, {"tokens": t}, last_only=True)[0][:, -1])
    worst = 0.0
    for out in run.tokens:
        _check(out.shape == (batch, prompt_len + new_tokens),
               f"tokens shape {out.shape}")
        _check(bool(((out >= 0) & (out < vocab)).all()),
               "token outside the vocabulary")
        prompts = out[:, :prompt_len]
        logits = np.asarray(gen.prefill(prompts)[0])
        ref = np.asarray(fwd(gen.params, jnp.asarray(prompts)))
        _check(bool(np.isfinite(logits).all()), "non-finite prefill logits")
        err = float(np.abs(logits - ref).max() / np.abs(ref).max())
        _check(err <= SERVE_LOGIT_TOL,
               f"prefill logits differ from forward by {err} of max|logit| "
               f"(tolerance {SERVE_LOGIT_TOL})")
        worst = max(worst, err)
    return {"dtype": str(jax.tree.leaves(gen.params)[0].dtype),
            "request_s": run.request_s, "logit_rel_err": worst}


# ------------------------------------------------------------------ codec --

def _int8_agreement(x):
    qk, _, _, ek = int8_roundtrip(x, backend="kernel")
    qr, _, dr, er = int8_roundtrip(x, backend="ref")
    # ref.quantize8_ef_ref: the residual matches to the last ulp (FMA)
    a = jnp.maximum(jnp.abs(x), jnp.abs(dr))
    ulp = jnp.nextafter(a, jnp.float32(jnp.inf)) - a
    return {"codes_equal": jnp.all(qk == qr),
            "residual_within_ulp": jnp.all(jnp.abs(ek - er) <= ulp),
            "residual_bit_equal": jnp.all(ek == er)}


def _topk_agreement(x, k):
    kk, rk = topk_ef(x, k, backend="kernel")
    kr, rr = topk_ef(x, k, backend="ref")
    return {"kept_equal": jnp.all(kk == kr), "residual_equal": jnp.all(rk == rr),
            "kept": jnp.sum(kk != 0)}


def codec_phase(n: int, *, seed: int = 0) -> dict:
    """int8 EF roundtrip and top-k EF on a seeded fp32 vector of n elements,
    kernel backend against the ref backend, in one program per codec on the
    same device.  ``mosaic`` says whether that program holds a Mosaic call
    (on a TPU the kernel backend lowers to one; elsewhere it interprets)."""
    x = jax.random.normal(jax.random.key(seed), (n,), jnp.float32)
    k = max(1, int(n * TOPK_FRACTION))
    out = {}
    for name, agree in (("int8_roundtrip", _int8_agreement),
                        ("topk_ef", lambda v: _topk_agreement(v, k))):
        program = jax.jit(agree).lower(x).compile()
        out[name] = {key: v.item() for key, v in program(x).items()}
        out[name]["mosaic"] = "tpu_custom_call" in program.as_text()
    i8, tk = out["int8_roundtrip"], out["topk_ef"]
    _check(i8["codes_equal"], "int8 codes differ between kernel and ref")
    _check(i8["residual_within_ulp"], "int8 residuals differ by over 1 ulp")
    _check(tk["kept_equal"] and tk["residual_equal"],
           "top-k kept/residual differ between kernel and ref")
    _check(tk["kept"] >= k, f"top-k kept {tk['kept']} < k={k}")
    return out


# -------------------------------------------------------------- four chips --

def _spread(tree, n: int, *, split: bool) -> bool:
    """Every leaf has shards on n devices; with ``split`` the largest leaf
    is also cut into n pieces (not n full copies)."""
    leaves = jax.tree.leaves(tree)
    if any(len({s.device for s in x.addressable_shards}) != n for x in leaves):
        return False
    big = max(leaves, key=lambda x: x.size)
    return not split or big.addressable_shards[0].data.size * n == big.size


def four_chip_phase(arch: ArchConfig, devices, *, batch: int, seq: int,
                    steps: int) -> dict:
    """Data-parallel train on a (n,1) mesh under both comm patterns, against
    the same steps on a 1x1 mesh of devices[0]."""
    n = len(devices)
    ref = train(arch, make_data_mesh(devices[:1]), steps=steps,
                batch_size=batch, seq=seq, log_every=1).losses
    out = {"one_device": ref}
    for pattern in ("allreduce", "scatter_reduce"):
        a = arch.replace(train=dataclasses.replace(arch.train,
                                                   comm_pattern=pattern))
        run = train(a, make_data_mesh(devices), steps=steps,
                    batch_size=batch, seq=seq, log_every=1)
        _check(_spread(run.batch, n, split=True),
               f"{pattern}: batch not split over {n} devices")
        _check(_spread(run.params, n, split=pattern == "scatter_reduce"),
               f"{pattern}: parameters not placed on {n} devices")
        diff = max(abs(x - y) for x, y in zip(run.losses, ref))
        _check(diff <= LOSS_TOL_BF16,
               f"{pattern}: losses {run.losses} vs one device {ref} "
               f"differ by {diff} (> {LOSS_TOL_BF16})")
        out[pattern] = {"losses": run.losses, "max_loss_diff": diff,
                        "steady_step_s": statistics.median(run.step_s[1:])}
    return out


# ------------------------------------------------------------------- main --

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the data-parallel train check on a "
                         "four-chip host")
    args = ap.parse_args(argv)
    try:
        devices = jax.devices()
        dev = device_info(devices)
        check_codec_backend()
        if args.four_chips:
            _check(dev["count"] == 4, f"--four-chips needs 4 devices, "
                                      f"JAX found {dev['count']}")
    except SmokeFailure as e:
        print(f"chip_smoke: {e}", file=sys.stderr)
        return 1
    enable_compile_cache()
    arch = get_arch(ARCH)
    label = "one-run smoke numbers, not benchmark numbers"

    if args.four_chips:
        r = four_chip_phase(arch, devices, batch=TRAIN_BATCH, seq=TRAIN_SEQ,
                            steps=FOUR_CHIP_STEPS)
        for pattern in ("allreduce", "scatter_reduce"):
            print(f"four_chips {pattern}: losses {r[pattern]['losses']} vs "
                  f"one device {r['one_device']} (max diff "
                  f"{r[pattern]['max_loss_diff']}, tolerance "
                  f"{LOSS_TOL_BF16}); steady step "
                  f"{r[pattern]['steady_step_s']} s [{label}]")
    else:
        t = train_phase(arch, devices[0], batch=TRAIN_BATCH, seq=TRAIN_SEQ,
                        steps=TRAIN_STEPS)
        print(f"train: losses {t['losses']}")
        print(f"train: steady step {t['steady_step_s']} s, "
              f"{t['tokens_per_s']} tokens/s, first-step excess (compile) "
              f"{t['compile_s']} s, peak_bytes_in_use "
              f"{t['peak_bytes_in_use']} [{label}]")
        s = serve_phase(arch, requests=SERVE_REQUESTS, batch=SERVE_BATCH,
                        prompt_len=PROMPT_LEN, new_tokens=NEW_TOKENS)
        print(f"serve: dtype {s['dtype']}, prefill logits vs forward "
              f"{s['logit_rel_err']} of max|logit| (tolerance "
              f"{SERVE_LOGIT_TOL}); request times {s['request_s']} s "
              f"[{label}]")
        c = codec_phase(build_model(arch).param_count())
        _check(c["int8_roundtrip"]["mosaic"] and c["topk_ef"]["mosaic"],
               f"a codec kernel ran without Mosaic: {c}")
        print(f"codec: {json.dumps(c)}")
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
