"""Every file the benchmark names is where its name says, and loads."""
import importlib
import json
import re

import pytest

from chipbench import bench, generator
from chipbench.reference import family

SPEC = bench.spec()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_names_and_keys_follow_the_contract():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs", "workloads",
                         "end_to_end", "per_layer"}
    names = [x["name"] for x in SPEC["configs"] + SPEC["workloads"]
             + SPEC["end_to_end"] + SPEC["per_layer"]]
    assert all(NAME.match(n) for n in names) and len(names) == len(set(names))
    assert any(m["name"] == "setup_s" for m in SPEC["end_to_end"])
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    assert all(m["moves"] in e2e for m in SPEC["per_layer"])
    assert len((bench.ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024


@pytest.mark.parametrize("cfg", SPEC["configs"], ids=lambda c: c["name"])
def test_config_file_states_what_runs(cfg):
    data = json.loads((bench.ROOT / cfg["file"]).read_text())
    assert data["source"] == cfg["source"]
    assert sorted(data["reduced"]) == sorted(cfg["reduced"])
    fam = family(data["family"])
    import jax

    from chipbench.cell import program_arch
    from chipbench.reference.training import init
    from repro.models import build_model

    arch = program_arch(data, fam, {})
    for field, value in fam.program_fields(data).items():
        assert getattr(arch.model, field) == value, field
    # the model the harness builds from the file has the reference's layout
    shapes = lambda t: {jax.tree_util.keystr(k): v.shape
                        for k, v in jax.tree_util.tree_leaves_with_path(t)}
    assert shapes(build_model(arch).abstract()) == shapes(
        jax.eval_shape(lambda: init(data, fam, 0)))


@pytest.mark.parametrize("cell", SPEC["workloads"], ids=lambda c: c["name"])
def test_every_cell_finds_its_files(cell):
    assert bench.config(cell["config"])
    mix = generator.load(cell["traffic"])
    importlib.import_module(f"chipbench.{mix['kind']}_cell")
    assert bench.limits(cell["name"])
    e2e = bench.metrics_of(cell["name"], traced=False)
    assert "setup_s" in {m["name"] for m in e2e} and len(e2e) >= 2
    assert bench.metrics_of(cell["name"], traced=True)


@pytest.mark.parametrize("metric", SPEC["end_to_end"] + SPEC["per_layer"],
                         ids=lambda m: m["name"])
def test_every_metric_has_a_reader(metric):
    mod = importlib.import_module(f"chipbench.metrics.{metric['name']}")
    assert callable(mod.read)


def test_generator_is_seeded_and_every_seed_offers_the_same_work():
    mix = generator.load("serve-b16-chat")
    a = generator.serve_requests(mix, 1000, 2**31 + 11, 0)
    b = generator.serve_requests(mix, 1000, 2**31 + 11, 0)
    c = generator.serve_requests(mix, 1000, 7, 0)
    assert [r.prompts.tolist() for r in a] == [r.prompts.tolist() for r in b]
    assert sorted(r.steps for r in a) == sorted(r.steps for r in c)
    assert max(r.steps for r in a) <= mix["max_seq"]
    train = generator.load("train-8x2k")
    x = generator.train_batch(train, 49152, 2**31 + 11, 0)
    y = generator.train_batch(train, 49152, 2**31 + 11, 1)
    assert x["tokens"].shape == (8, 2048)
    assert (x["labels"][:, :-1] == x["tokens"][:, 1:]).all()
    assert len({r.tobytes() for r in list(x["tokens"]) + list(y["tokens"])}) == 16
