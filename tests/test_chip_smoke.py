"""chip_smoke.py's phases on the CPU at reduced width: the script's control
flow and checks, guarded without a chip.  The device check must refuse the
CPU; on the CPU the codec kernels run in interpret mode (no Mosaic call)."""
import importlib.util
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def arch():
    from repro.configs import get_reduced
    return get_reduced("smollm-360m")


def test_device_check_refuses_cpu(smoke):
    with pytest.raises(smoke.SmokeFailure, match="no TPU"):
        smoke.device_info(jax.devices("cpu"))
    with pytest.raises(smoke.SmokeFailure, match="REPRO_CODEC_BACKEND"):
        smoke.check_codec_backend({"REPRO_CODEC_BACKEND": "ref"})
    smoke.check_codec_backend({})
    assert smoke.main([]) == 1


def test_train_phase(smoke, arch):
    r = smoke.train_phase(arch, jax.devices()[0], batch=8, seq=64, steps=5)
    assert len(r["losses"]) == len(r["step_s"]) == 5
    assert r["losses"][-1] < r["losses"][0]
    assert r["tokens_per_s"] > 0


def test_serve_phase(smoke, arch):
    r = smoke.serve_phase(arch, requests=2, batch=4, prompt_len=8,
                          new_tokens=8)
    assert r["dtype"] == "float32"
    assert len(r["request_s"]) == 2
    assert r["logit_rel_err"] <= smoke.SERVE_LOGIT_TOL


@pytest.mark.parametrize("n", [1000, 96_300])
def test_codec_phase(smoke, n):
    r = smoke.codec_phase(n)
    for name in ("int8_roundtrip", "topk_ef"):
        assert r[name]["mosaic"] is False       # interpret mode off the TPU
    assert r["int8_roundtrip"]["codes_equal"]
    assert r["topk_ef"]["kept"] >= n // 100


def test_four_chip_phase_on_host_devices():
    """The --four-chips path on four host devices (one process per device
    count, so a subprocess): placement and loss agreement checks pass."""
    script = r"""
import importlib.util, json, jax
from repro.configs import get_reduced
spec = importlib.util.spec_from_file_location("chip_smoke", "chip_smoke.py")
smoke = importlib.util.module_from_spec(spec); spec.loader.exec_module(smoke)
r = smoke.four_chip_phase(get_reduced("smollm-360m"), jax.devices(),
                          batch=8, seq=64, steps=3)
print(json.dumps(r))
"""
    env = {**os.environ, "PYTHONPATH": str(ROOT / "src"),
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    r = subprocess.run([sys.executable, "-c", script], env=env, cwd=ROOT,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stdout + r.stderr
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert len(out["one_device"]) == 3
    for pattern in ("allreduce", "scatter_reduce"):
        assert len(out[pattern]["losses"]) == 3
