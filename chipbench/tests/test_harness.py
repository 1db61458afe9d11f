"""The harness refuses what is not a run on the chip, and drives a whole run
at reduced size past the look for a chip: sound, it is correct; with the
timed path broken underneath, or with the control in the program's place,
it is not."""
import json
import shutil
import subprocess
import sys
import time

import jax
import pytest

from chipbench import bench, compare
from chipbench.reference import family, training
from chipbench.reference.common import FP8
from chipbench.run import Refused, devices_for, run_cell
from chipbench.tests.sizes import tiny_config, tiny_mix
from chipbench import generator

TRAIN = ["smollm-360m.train-8x2k", "mamba2-370m.train-8x2k"]
SERVE = "smollm-360m.serve-b16-chat"


def test_refuses_a_cpu_device():
    with pytest.raises(Refused, match="no TPU"):
        devices_for(1)


def _run_cli(cwd, *args):
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "smollm-360m.train-8x2k",
         "--seed", "1", "--seconds", "1", "--trace", "0", *args],
        cwd=cwd, capture_output=True, text=True, timeout=300,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"})


def test_cli_prints_no_result_on_the_cpu():
    r = _run_cli(bench.ROOT)
    assert r.returncode == 2 and r.stdout == "" and "no TPU" in r.stderr


def test_cli_prints_no_result_without_the_program(tmp_path):
    shutil.copy(bench.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(bench.HERE, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    r = _run_cli(tmp_path)
    assert r.returncode == 2 and r.stdout == ""


def _run(name, fault=None, seed=2**31 + 3):
    cell = bench.workload(name)
    return run_cell(cell, seed, 0.2, False, jax.devices(), time.perf_counter(),
                    fault=fault, log=lambda *a: None,
                    config=tiny_config(cell["config"]), mix=tiny_mix(cell["traffic"]))


@pytest.mark.parametrize("name", TRAIN)
@pytest.mark.parametrize("fault", [None, "unchanged", "half_batch"])
def test_train_run_is_correct_only_when_sound(name, fault):
    r = _run(name, fault)
    json.dumps(r)
    assert list(r)[-1] == "checks"
    assert r["correct"] is (fault is None), r["checks"]
    assert r["attempted"] >= 1 and "train_tokens_per_s" in r["metrics"]


@pytest.mark.parametrize("fault", [None, "altered_token"])
def test_serve_run_is_correct_only_when_sound(fault):
    r = _run(SERVE, fault)
    assert r["correct"] is (fault is None), r["checks"]
    assert r["attempted"] >= 2 and "serve_tokens_per_s" in r["metrics"]


@pytest.mark.parametrize("name", TRAIN)
def test_train_control_fails_the_limits(name):
    cell = bench.workload(name)
    c, mix = tiny_config(cell["config"]), tiny_mix(cell["traffic"])
    fam = family(c["family"])
    batches = [generator.train_batch(mix, fam.program_fields(c)["vocab_size"], 5, k)
               for k in range(mix["check_steps"])]
    kw = dict(block_rows=mix["reference_rows"])
    ref = training.train_steps(c, fam, 5, batches, mix["optimizer"], **kw)
    ctrl = training.train_steps(c, fam, 5, batches, mix["optimizer"], num=FP8, **kw)
    assert not compare.judge(compare.train_numbers(ctrl, ref), bench.limits(name))


def test_serve_control_fails_the_limits():
    import numpy as np

    from chipbench.reference import serving

    cell = bench.workload(SERVE)
    c, mix = tiny_config(cell["config"]), tiny_mix(cell["traffic"])
    seqs = np.random.default_rng(0).integers(0, 256, (8, 48)).astype(np.int32)
    gap = serving.widest_gap(c, family(c["family"]), 5, seqs, 8, control=FP8)
    assert not compare.judge({"served_logit_gap": gap}, bench.limits(SERVE))
