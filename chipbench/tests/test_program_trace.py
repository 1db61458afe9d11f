"""The program's own names for its layers: the named scopes reach every
instruction of the train and decode steps they cover, through the backward
pass, the recompute and the layer scan; ``Generator`` writes its spans; the
reduction by scope and span adds up, on synthetic intervals and on a trace
recorded on one v5e chip."""
import gzip
from pathlib import Path

import jax
import numpy as np
import pytest

from chipbench import bench, trace
from chipbench import program_trace as pt
from chipbench.tests.sizes import tiny_config, tiny_mix
from chipbench.train_cell import SPANS

DATA = Path(__file__).resolve().parents[1] / "testdata"
TRAIN = {"smollm-360m.train-8x2k": {"attn": "fbr", "mlp": "fbr", "head": "fb",
                                    "loss": "fb", "optimizer": "f"},
         "mamba2-370m.train-8x2k": {"ssd_scan": "fbr", "head": "fb", "loss": "fb",
                                    "optimizer": "f"}}


def _tiny_rec(name):
    cell = bench.workload(name)
    return bench.Record(workload=name, config=tiny_config(cell["config"]),
                        mix=tiny_mix(cell["traffic"]), device_kind="cpu", chips=1)


def _kind(path):
    """'b' backward, 'r' recompute under remat, 'f' forward."""
    if "transpose(" in path:
        return "r" if "rematted_computation" in path else "b"
    return "f"


def test_transform_wrappers_are_stripped():
    assert pt.strip("transpose(jvp(attn))") == "attn"
    assert pt.strip("jvp()") == "" and pt.strip("checkpoint") == "checkpoint"
    path = ("jit(train_step)/transpose(jvp())/while/body/closed_call/checkpoint/"
            "rematted_computation/attn/flashrgn/div")
    assert pt.scopes_in(path) == ["attn"]
    assert pt.scopes_in("jit(train_step)/transpose(jvp(head))/bsd,dv->bsv/dot_general") == [
        "head"]
    assert pt.scopes_in("params['blocks']['attn']['wk']") == []
    # inside a nested jit a segment can be the caller's function name, not a scope
    assert pt.scopes_in("jit(train_step)/jvp()/closed_call/jit(cumsum)/ssd_scan/r") == []
    assert pt.scopes_in("jit(train_step)/ssd_scan/jit(cumsum)/ssd_scan/r") == ["ssd_scan"]
    ops = pt.hlo_ops('  %fusion.3 = (f32[2]{0}, u32[]) fusion(%p), kind=kLoop, '
                     'metadata={op_name="jit(f)/jvp(mlp)/mul" source_line=3}\n'
                     '  ROOT %copy.1 = f32[2]{0} copy(%fusion.3)\n')
    assert ops == {"fusion.3": ("(f32[2]{0}, u32[])", "jit(f)/jvp(mlp)/mul"),
                   "copy.1": ("f32[2]{0}", "")}
    assert pt.op_scopes(ops) == {"fusion.3": "mlp"}


@pytest.mark.parametrize("name", sorted(TRAIN))
def test_scopes_reach_forward_backward_and_recompute(name):
    ops = pt.hlo_ops(pt.train_hlo(_tiny_rec(name)))
    seen = {}
    for _, path in ops.values():
        found = pt.scopes_in(path)
        assert len(set(found)) <= 1, path
        if found:
            seen.setdefault(found[0], set()).add(_kind(path))
    assert seen == {s: set(k) for s, k in TRAIN[name].items()}
    if "attn" in seen:  # the dry-run analyzer's marker stays inside the scope
        assert any("/attn/flashrgn/" in p for _, p in ops.values())


def test_rebuilt_scopes_come_from_this_tree_not_the_compile_cache(tmp_path, monkeypatch):
    """The persistent cache's key leaves out op metadata by default, so the
    same step compiled from a tree without the scopes must not be read back."""
    import contextlib

    from jax._src import compilation_cache

    rec = _tiny_rec("smollm-360m.train-8x2k")
    keys = ("jax_compilation_cache_dir", "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    was = {k: getattr(jax.config, k) for k in keys}
    try:
        for k, v in zip(keys, (str(tmp_path), 0, -1)):
            jax.config.update(k, v)
        compilation_cache.reset_cache()
        with monkeypatch.context() as m:
            m.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
            assert pt.op_scopes(pt.hlo_ops(pt.train_hlo(rec))) == {}
        assert list(tmp_path.iterdir())   # the unscoped step is in the cache
        assert set(pt.op_scopes(pt.hlo_ops(pt.train_hlo(rec))).values()) == set(
            TRAIN["smollm-360m.train-8x2k"])
    finally:
        for k, v in was.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()


def test_cli_refuses_without_a_tpu(capsys):
    assert pt.main(["--workload", "smollm-360m.train-8x2k"]) == 2
    assert "no TPU" in capsys.readouterr().err


def test_decode_step_names_attention_mlp_and_head():
    text = pt.decode_hlo(_tiny_rec("smollm-360m.serve-b16-chat"))
    assert set(pt.op_scopes(pt.hlo_ops(text)).values()) == {"attn", "mlp", "head"}


def test_generator_writes_its_spans_on_the_host_plane(tmp_path):
    from jax.profiler import ProfileData

    from chipbench.cell import program_arch
    from repro.models import build_model
    from repro.serving import Generator

    rec = _tiny_rec("smollm-360m.serve-b16-chat")
    arch = program_arch(rec.config, rec.family, rec.mix, dtype=rec.mix["dtype"])
    gen = Generator(arch, jax.jit(build_model(arch).init)(jax.random.key(0)), max_seq=16)
    reqs = [(np.ones((2, 3), np.int32), 2), (np.ones((2, 5), np.int32), 4)]
    gen.generate(*reqs[0])
    with jax.profiler.trace(str(tmp_path)):
        for prompts, new in reqs:
            gen.generate(prompts, max_new_tokens=new)
    found = [(plane.name, ev.name)
             for plane in ProfileData.from_file(str(trace.find_xplane(tmp_path))).planes
             for line in plane.lines for ev in line.events if ev.name in pt.PROGRAM_SPANS]
    assert {plane for plane, _ in found} == {"/host:CPU"}
    counts = {n: sum(1 for _, m in found if m == n) for n in pt.PROGRAM_SPANS}
    assert counts == {"prefill": 2, "sample": 6, "token_fetch": 6, "decode": 6}


def test_scope_seconds_on_synthetic_ops():
    ops = [(0, 40, "while.1"), (0, 10, "fusion.1"), (10, 20, "fusion.2"),
           (20, 25, "copy.3"), (30, 40, "fusion.4")]
    scopes = {"fusion.1": "attn", "fusion.2": "mlp"}
    got = pt.scope_seconds(ops, scopes, 5, 35)
    # 25-30 runs only the loop op: unscoped with copy.3 and the clipped fusion.4
    assert got == pytest.approx({"attn": 5e-9, "mlp": 10e-9, pt.UNSCOPED: 15e-9})
    assert sum(got.values()) == pytest.approx(30e-9)
    got = pt.scope_seconds(ops, scopes, 5, 35, modules=[(0, 26)])
    assert got == pytest.approx({"attn": 5e-9, "mlp": 10e-9, pt.UNSCOPED: 10e-9,
                                 pt.OTHER: 5e-9})


def test_span_idle_on_synthetic_intervals():
    busy = [[10, 20], [30, 40]]
    spans = [(0, 15, "prefill"), (15, 25, "sample"), (25, 45, "decode"), (47, 60, "decode")]
    span_s, span_n, idle = pt.span_seconds(spans, busy, 0, 50)
    assert span_s == pytest.approx({"prefill": 15e-9, "sample": 10e-9, "decode": 23e-9})
    assert span_n == {"prefill": 1, "sample": 1, "decode": 2}
    # the gap 20-30 straddles sample and decode; 45-47 lies outside every span
    assert idle == pytest.approx({"prefill": 10e-9, "sample": 5e-9, "decode": 13e-9})
    assert sum(idle.values()) + 2e-9 == pytest.approx(30e-9)


def _read(name, rec):
    return bench.read_metric({"name": name}, rec)


TRAIN_READERS = ("train_attn_ms", "train_mlp_ms", "train_ssd_scan_ms", "train_head_loss_ms",
                 "train_optimizer_ms")
SERVE_READERS = ("serve_prefill_ms_per_step", "serve_prefill_idle_ms_per_step",
                 "serve_decode_idle_ms_per_step")


def test_train_readers_give_device_ms_per_step():
    rec = _tiny_rec("smollm-360m.train-8x2k")
    rec.trace, rec.traced = object(), {"steps": 3}
    rec._program_trace = pt.ProgramTrace(
        window_s=3.0, busy_s=2.9, idle_s=0.1,
        scope_s={"attn": 1.5, "mlp": 0.6, "head": 0.1, "loss": 0.05, "optimizer": 0.03,
                 pt.UNSCOPED: 0.62})
    got = {n: _read(n, rec) for n in TRAIN_READERS}
    assert got == pytest.approx({"train_attn_ms": 500.0, "train_mlp_ms": 200.0,
                                 "train_ssd_scan_ms": None, "train_head_loss_ms": 50.0,
                                 "train_optimizer_ms": 10.0})
    rec._program_trace.unmatched_s = 0.1   # not the program that was traced
    assert all(_read(n, rec) is None for n in TRAIN_READERS)
    # a program without the scopes: every op unscoped
    rec._program_trace = pt.ProgramTrace(window_s=3.0, busy_s=2.9, idle_s=0.1,
                                         scope_s={pt.UNSCOPED: 2.9})
    assert all(_read(n, rec) is None for n in TRAIN_READERS)


def test_serve_readers_divide_by_positions_and_steps():
    rec = _tiny_rec("smollm-360m.serve-b16-chat")
    rec.traced = {"requests": [(16, 5, 3), (16, 4, 2)]}
    rec.trace = trace.Reduction(window_s=1.0, busy_s=0.9, module_s={},
                                module_calls={"jit_decode_step": 14})
    rec._program_trace = pt.ProgramTrace(
        window_s=1.0, busy_s=0.9, idle_s=0.1,
        span_s={"prefill": 0.09, "sample": 0.01, "token_fetch": 0.3, "decode": 0.02},
        span_n={"prefill": 2, "sample": 5, "token_fetch": 5, "decode": 5},
        span_idle_s={"prefill": 0.009, "sample": 0.001, "token_fetch": 0.003,
                     "decode": 0.001})
    got = {n: _read(n, rec) for n in SERVE_READERS}
    assert got == pytest.approx({"serve_prefill_ms_per_step": 10.0,
                                 "serve_prefill_idle_ms_per_step": 1.0,
                                 "serve_decode_idle_ms_per_step": 1.0})
    rec.trace.module_calls["jit_decode_step"] = 13   # matches neither layout
    assert all(_read(n, rec) is None for n in SERVE_READERS)
    rec.trace.module_calls["jit_decode_step"] = 14
    rec._program_trace.span_n = rec._program_trace.span_s = {}  # a program without spans
    assert all(_read(n, rec) is None for n in SERVE_READERS)


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """The reduction of the trace recorded on a v5e by
    ``chipbench/testdata/record_program_spans.py``: two tiny dense train
    steps, then a two-token generate, in one window."""
    path = tmp_path_factory.mktemp("recorded") / "program_spans.xplane.pb"
    path.write_bytes(gzip.decompress((DATA / "program_spans.xplane.pb.gz").read_bytes()))
    hlo = gzip.decompress((DATA / "program_spans.hlo.txt.gz").read_bytes()).decode()
    return path, pt.reduce_program(path, pt.hlo_ops(hlo), pt.TRAIN_MODULE)


def test_recorded_scopes_add_up_to_the_busy_time(recorded):
    path, red = recorded
    assert red.unmatched_s == 0      # every op of the step has the HLO's result type
    assert red.busy_s == pytest.approx(trace.reduce_trace(path, SPANS).busy_s)
    assert set(red.scope_s) == {"attn", "mlp", "head", "loss", "optimizer", pt.UNSCOPED,
                                pt.OTHER}
    assert sum(red.scope_s.values()) == pytest.approx(red.busy_s, rel=1e-6)
    assert red.scope_s["attn"] == pytest.approx(28.037e-6)
    assert red.scope_s["optimizer"] == pytest.approx(10.439e-6)
    # the generate's decode, argmax and cache programs lie outside the train step
    assert red.scope_s[pt.OTHER] == pytest.approx(50.774e-6)


def test_recorded_spans_and_the_idle_inside_them(recorded):
    _, red = recorded
    assert red.span_n == {"prefill": 1, "sample": 2, "token_fetch": 2, "decode": 2}
    assert red.span_s["prefill"] == pytest.approx(5.8311e-3)
    assert red.span_idle_s["prefill"] == pytest.approx(5.8005e-3)
    assert red.span_idle_s["token_fetch"] == pytest.approx(0.844219e-3)
    assert red.idle_s == pytest.approx(red.window_s - red.busy_s)
    # the rest of the window's idle lies in the train steps, outside every program span
    assert red.idle_s - sum(red.span_idle_s.values()) == pytest.approx(5.963681e-3)
