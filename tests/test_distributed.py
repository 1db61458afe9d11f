"""Multi-device distribution tests.

jax pins the device count at first init, so anything needing >1 device runs
in a SUBPROCESS with REPRO_XLA_FLAGS / XLA_FLAGS set before the jax import
(same mechanism as the dry-run launcher).  These are integration tests of
the real launcher path on reduced configs -- slow-ish (~2 min total).
"""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
ENV = {**os.environ,
       "REPRO_XLA_FLAGS": "--xla_force_host_platform_device_count=8",
       "PYTHONPATH": str(ROOT / "src")}


def _run(args, timeout=900):
    return subprocess.run([sys.executable, *args], env=ENV, cwd=ROOT,
                          capture_output=True, text=True, timeout=timeout)


@pytest.mark.parametrize("arch,shape", [
    ("smollm-360m", "train_4k"),
    ("deepseek-v2-lite-16b", "train_4k"),   # MoE + MLA + EP
    ("zamba2-2.7b", "decode_32k"),          # hybrid cache
])
def test_dryrun_reduced_single_pod(arch, shape):
    r = _run(["-m", "repro.launch.dryrun", "--arch", arch, "--shape", shape,
              "--mesh", "2x4", "--reduced", "--no-save"])
    assert r.returncode == 0, r.stdout + r.stderr
    assert "ok " in r.stdout


def test_dryrun_reduced_multi_pod():
    r = _run(["-m", "repro.launch.dryrun", "--arch", "smollm-360m",
              "--shape", "train_4k", "--mesh", "2x2x2", "--reduced",
              "--no-save"])
    assert r.returncode == 0, r.stdout + r.stderr
    assert "ok " in r.stdout


def test_local_sgd_no_cross_pod_collectives_in_inner_step():
    """The heart of the MA-SGD-on-pods claim: the inner step's collectives
    must all stay within a pod (replica groups never span pods)."""
    script = r"""
import os
os.environ["XLA_FLAGS"]="--xla_force_host_platform_device_count=8"
import json, jax
from repro.configs import get_reduced
from repro.configs.base import ShapeConfig
from repro.launch.mesh import make_mesh
from repro.distributed.local_sgd import build_local_sgd
from repro.distributed.hlo_analysis import analyze_hlo
mesh = make_mesh((2,2,2),("pod","data","model"))
ls = build_local_sgd(get_reduced("smollm-360m"), mesh, ShapeConfig("t",128,8,"train"))
with mesh:
    inner = analyze_hlo(ls.lower_inner().compile().as_text(), pod_size=4)
    outer = analyze_hlo(ls.lower_outer().compile().as_text(), pod_size=4)
print(json.dumps({"inner_cross": inner["cross_pod_bytes"],
                  "inner_total": inner["coll_bytes"],
                  "outer_cross": outer["cross_pod_bytes"]}))
"""
    r = _run(["-c", script])
    assert r.returncode == 0, r.stdout + r.stderr
    out = json.loads(r.stdout.strip().splitlines()[-1])
    # the MA-SGD-on-pods guarantee: ZERO cross-pod bytes in the inner step,
    # while the outer sync does cross pods
    assert out["inner_cross"] == 0, out
    assert out["inner_total"] > 0 and out["outer_cross"] > 0, out


def test_local_sgd_numerics_and_sync():
    """Inner loss decreases; after the outer step all pod replicas agree."""
    script = r"""
import os
os.environ["XLA_FLAGS"]="--xla_force_host_platform_device_count=8"
import json, jax, jax.numpy as jnp
from repro.configs import get_reduced
from repro.configs.base import ShapeConfig
from repro.launch.mesh import make_mesh
from repro.distributed.local_sgd import build_local_sgd
from repro.launch.specs import make_batch
from repro.models import build_model
from repro.optim import make_optimizer
mesh = make_mesh((2,2,2),("pod","data","model"))
arch = get_reduced("smollm-360m")
ls = build_local_sgd(arch, mesh, ShapeConfig("t",128,8,"train"))
model = build_model(arch)
params = model.init(jax.random.key(0))
params_st = jax.tree.map(lambda x: jnp.stack([x]*2), params)
opt = make_optimizer(arch.train)
opt_st = jax.tree.map(lambda x: jnp.stack([x]*2), opt.init(params))
batch = make_batch(arch, 8, 128)
with mesh:
    losses = []
    for _ in range(5):
        params_st, opt_st, m = ls.inner_fn(params_st, opt_st, batch)
        losses.append(float(m["loss"][0]))
    out_state = ls.init_outer_fn(params_st)
    params_st, out_state = ls.outer_fn(params_st, out_state)
    leaf = jax.tree.leaves(params_st)[2]
    eq = bool(jnp.allclose(leaf[0], leaf[1], atol=1e-3))
print(json.dumps({"losses": losses, "eq": eq}))
"""
    r = _run(["-c", script])
    assert r.returncode == 0, r.stdout + r.stderr
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["losses"][-1] < out["losses"][0]
    assert out["eq"]


def test_comm_pattern_changes_collectives():
    """allreduce (pure DP) vs scatter_reduce (FSDP): the FSDP lowering must
    contain reduce-scatter or param all-gathers; pure DP must not."""
    script = r"""
import os
os.environ["XLA_FLAGS"]="--xla_force_host_platform_device_count=8"
import dataclasses, json, jax
from repro.configs import get_reduced
from repro.configs.base import ShapeConfig
from repro.launch.mesh import make_mesh
from repro.distributed.step import build_train_step
from repro.distributed.hlo_analysis import analyze_hlo
mesh = make_mesh((4,2),("data","model"))
sh = ShapeConfig("t", 64, 16, "train")
out = {}
for pat in ("allreduce", "scatter_reduce"):
    arch = get_reduced("stablelm-3b")
    arch = arch.replace(train=dataclasses.replace(arch.train, comm_pattern=pat))
    step = build_train_step(arch, mesh, sh)
    with mesh:
        c = step.lower().compile()
    r = analyze_hlo(c.as_text())
    out[pat] = {k: v["count"] for k, v in r["coll"].items() if isinstance(v, dict)}
print(json.dumps(out))
"""
    r = _run(["-c", script])
    assert r.returncode == 0, r.stdout + r.stderr
    out = json.loads(r.stdout.strip().splitlines()[-1])
    fsdp = out["scatter_reduce"]
    assert fsdp["reduce-scatter"] + fsdp["all-gather"] > \
        out["allreduce"]["reduce-scatter"] + out["allreduce"]["all-gather"]
