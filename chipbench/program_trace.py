"""Per-layer reduction of a traced window by the program's own names: the
device time of each ``jax.named_scope`` and the host time and device idle
inside each of the program's ``TraceAnnotation`` spans.

It reads the same ``.xplane.pb`` as :mod:`chipbench.trace`, clips to the
same ``window`` span and takes the first chip, as that module's per-op
numbers do.  The profiler's events carry no scope, so an operation's scope
comes from the optimized HLO of the program that ran: the ``op_name`` of its
``metadata``.  A traced run's readers rebuild that program after the run
(``train_hlo``), so neither ``setup_s`` nor the timed window sees it.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field

from chipbench.trace import (
    WINDOW_SPAN, _CONTAINER, _host_spans, _length, _line, _union, find_xplane,
    module_name, op_name,
)

SCOPES = ("attn", "mlp", "ssd_scan", "head", "loss", "optimizer")
PROGRAM_SPANS = ("prefill", "sample", "token_fetch", "decode")
DECODE_SPANS = ("sample", "token_fetch", "decode")
UNSCOPED = "unscoped"
OTHER = "other programs"
TRAIN_MODULE = "jit_train_step"

_WRAPPER = re.compile(r"^[\w.\-]*\((.*)\)$")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([^\s=]+)\s*=\s*(.*)$")
_OP_NAME = re.compile(r'\bmetadata=\{[^}]*?\bop_name="([^"]*)"')


@dataclass
class ProgramTrace:
    window_s: float                  # length of the window span
    busy_s: float                    # first chip: union of its op intervals
    idle_s: float                    # window_s - busy_s
    scope_s: dict = field(default_factory=dict)       # scope -> device seconds, first chip
    span_s: dict = field(default_factory=dict)        # program span -> host seconds
    span_n: dict = field(default_factory=dict)        # program span -> count
    span_idle_s: dict = field(default_factory=dict)   # program span -> device idle inside it
    unmatched_s: float = 0.0         # op time whose result type differs from the HLO's


def strip(segment: str) -> str:
    """'transpose(jvp(attn))' -> 'attn': the name inside JAX's transform
    wrappers."""
    while m := _WRAPPER.match(segment):
        segment = m.group(1)
    return segment


def scopes_in(path: str) -> list:
    """The scope names among the segments of an ``op_name`` path, outermost
    first, up to the first jitted function nested in the program: inside one,
    JAX may write the calling Python function's name as a segment
    ('jit(cumsum)/ssd_scan/reduce_window_sum' in the function ``ssd_scan``)."""
    out = []
    for i, seg in enumerate(path.split("/")):
        if i and "jit(" in seg:
            break
        if strip(seg) in SCOPES:
            out.append(strip(seg))
    return out


def _result_type(rest: str) -> str:
    """'bf16[8,64]{1,0} fusion(...)' -> 'bf16[8,64]{1,0}'; a tuple type's
    spaces sit inside its parentheses."""
    depth = 0
    for i, ch in enumerate(rest):
        if ch in "([{":
            depth += 1
        elif ch in ")]}":
            depth -= 1
        elif ch == " " and depth == 0:
            return rest[:i]
    return rest


def hlo_ops(hlo_text: str) -> dict:
    """{instruction: (result type, op_name path or '')} of every instruction
    of an HLO module's text."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m:
            meta = _OP_NAME.search(m.group(2))
            out[m.group(1)] = (_result_type(m.group(2)), meta.group(1) if meta else "")
    return out


def op_scopes(ops: dict) -> dict:
    """{instruction: scope} for the instructions of ``hlo_ops`` whose path
    names a scope (the innermost, where it names more than one)."""
    out = {}
    for name, (_, path) in ops.items():
        found = scopes_in(path)
        if found:
            out[name] = found[-1]
    return out


def _intersect(a, b) -> float:
    """Length of the overlap of two merged interval lists."""
    i = j = 0
    total = 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def _idle(merged_busy, lo, hi) -> list:
    out, prev = [], lo
    for s, e in merged_busy + [[hi, hi]]:
        if s > prev:
            out.append([prev, s])
        prev = max(prev, e)
    return out


def scope_seconds(ops, scopes: dict, lo, hi, modules=None) -> dict:
    """{scope: seconds} of the leaf ops [(start, end, instruction)] in [lo, hi).

    An op with no scope counts as ``UNSCOPED``; so does busy time that only
    a loop or call op covers (its control between body ops).  Where
    ``modules`` [(start, end)] is given, ops outside those program runs
    count as ``OTHER``.  The values sum to the union of the ops' intervals
    wherever leaf ops do not overlap."""
    clip = [(max(s, lo), min(e, hi), n) for s, e, n in ops if e > lo and s < hi]
    runs = _union(modules) if modules is not None else None
    out, leaves = {}, []
    for s, e, name in clip:
        if _CONTAINER.match(name):
            continue
        leaves.append((s, e))
        if runs is not None and not _intersect([[s, e]], runs):
            key = OTHER
        else:
            key = scopes.get(name, UNSCOPED)
        out[key] = out.get(key, 0.0) + (e - s) / 1e9
    loops = _length(_union([(s, e) for s, e, _ in clip])) - _length(_union(leaves))
    if loops > 0:
        out[UNSCOPED] = out.get(UNSCOPED, 0.0) + loops / 1e9
    return out


def span_seconds(spans, merged_busy, lo, hi):
    """Host seconds, count and device idle seconds of each host span name,
    over [lo, hi): spans [(start, end, name)], busy as merged intervals."""
    idle = _idle(merged_busy, lo, hi)
    by_name = {}
    for s, e, name in spans:
        if e > lo and s < hi:
            by_name.setdefault(name, []).append((max(s, lo), min(e, hi)))
    span_s, span_n, span_idle = {}, {}, {}
    for name, ivs in by_name.items():
        span_s[name] = sum(e - s for s, e in ivs) / 1e9
        span_n[name] = len(ivs)
        span_idle[name] = _intersect(idle, _union(ivs)) / 1e9
    return span_s, span_n, span_idle


def reduce_program(path, hlo: dict | None = None, module: str | None = None) -> ProgramTrace:
    """Reduce the trace at ``path`` over its window span, first chip.
    ``hlo`` is ``hlo_ops`` of ``module``, the program whose ops get scopes."""
    from jax.profiler import ProfileData

    planes = list(ProfileData.from_file(str(path)).planes)
    windows = _host_spans(planes, {WINDOW_SPAN})
    if not windows:
        raise ValueError(f"trace {path} has no {WINDOW_SPAN!r} span")
    lo, hi = min(w[0] for w in windows), max(w[1] for w in windows)
    chips = sorted((p for p in planes if re.match(r"^/device:TPU:\d+$", p.name)),
                   key=lambda p: int(p.name.rsplit(":", 1)[1]))
    if not chips:
        raise ValueError(f"trace {path} has no TPU device plane")
    events = [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
              for ev in _line(chips[0], "XLA Ops")]
    ops = [(s, e, op_name(n)) for s, e, n in events]
    merged = _union([(max(s, lo), min(e, hi)) for s, e, _ in ops if e > lo and s < hi])
    busy = _length(merged)
    red = ProgramTrace(window_s=(hi - lo) / 1e9, busy_s=busy / 1e9,
                       idle_s=(hi - lo - busy) / 1e9)
    if hlo is not None:
        runs = [(ev.start_ns, ev.start_ns + ev.duration_ns)
                for ev in _line(chips[0], "XLA Modules") if module_name(ev.name) == module]
        red.scope_s = scope_seconds(ops, op_scopes(hlo), lo, hi, runs)
        for s, e, n in events:
            got = hlo.get(op_name(n))
            if (e > lo and s < hi and any(a <= s < b for a, b in runs)
                    and (got is None or got[0] != _result_type(n.split(" = ", 1)[-1]))):
                red.unmatched_s += (min(e, hi) - max(s, lo)) / 1e9
    red.span_s, red.span_n, red.span_idle_s = span_seconds(
        _host_spans(planes, set(PROGRAM_SPANS)), merged, lo, hi)
    return red


def train_step(rec, devices):
    """(the train cell's step, built as the cell builds it, on ``devices``;
    its mesh)."""
    import jax
    import jax.numpy as jnp
    from repro.configs.base import ShapeConfig
    from repro.distributed.step import build_train_step
    from repro.launch.mesh import make_data_mesh

    from chipbench.cell import program_arch

    mix = rec.mix
    B, S = mix["global_batch"], mix["seq_len"]
    arch = program_arch(rec.config, rec.family, mix)
    mesh = make_data_mesh(devices)
    specs = {k: jax.ShapeDtypeStruct((B, S), jnp.int32) for k in ("tokens", "labels")}
    return build_train_step(arch, mesh, ShapeConfig("chipbench", S, B, "train"),
                            batch_specs=specs), mesh


def compiled_text(lowered) -> str:
    """Optimized HLO text of ``lowered``, compiled under a cache key that
    holds the op metadata.  The persistent compile cache's key leaves it out
    by default, so a hit on a program that differs only in its names (the
    same step from a tree with other scopes) would hand back that program's
    ``op_name``s."""
    import jax

    key = "jax_compilation_cache_include_metadata_in_key"
    was = getattr(jax.config, key)
    jax.config.update(key, True)
    try:
        return lowered.compile().as_text()
    finally:
        jax.config.update(key, was)


def train_hlo(rec, devices=None) -> str:
    """Optimized HLO text of the train cell's step."""
    import jax

    step, mesh = train_step(rec, devices or jax.devices()[: rec.chips])
    with mesh:
        return compiled_text(step.lower())


def decode_hlo(rec) -> str:
    """Optimized HLO text of the serve cell's decode step, as
    ``Generator.decode_fn`` compiles it for the mix's batch."""
    import jax
    import jax.numpy as jnp
    from repro.serving import Generator

    from chipbench.cell import program_arch

    mix = rec.mix
    arch = program_arch(rec.config, rec.family, mix, dtype=mix["dtype"])
    gen = Generator(arch, None, max_seq=mix["max_seq"])
    scalar = jax.ShapeDtypeStruct((), jnp.int32)
    return compiled_text(gen.decode_fn.lower(
        gen.model.abstract(), gen.model.init_cache(mix["batch"], mix["max_seq"], abstract=True),
        jax.ShapeDtypeStruct((mix["batch"],), jnp.int32), scalar))


def of(rec) -> ProgramTrace | None:
    """The program reduction of a traced run, made once per record; None
    where the run was not traced."""
    if rec.trace is None:
        return None
    got = getattr(rec, "_program_trace", None)
    if got is None:
        from chipbench.cell import TRACE_DIR

        path = find_xplane(TRACE_DIR / rec.workload)
        if rec.kind == "train":
            got = reduce_program(path, hlo_ops(train_hlo(rec)), TRAIN_MODULE)
        else:
            got = reduce_program(path)
        rec._program_trace = got
    return got


def is_traced_program(red: ProgramTrace) -> bool:
    """Whether the recompiled program is the one traced: at most 1% of the
    busy time lies in ops it lacks or gives another result type."""
    return red.unmatched_s <= 0.01 * red.busy_s


def train_scope_ms(rec, *scopes) -> float | None:
    """Device ms of ``scopes`` per traced train step; None where the step
    names none of them, or where the rebuilt program is not the one traced
    (over 1% of busy time in ops of another result type)."""
    if rec.kind != "train" or not rec.traced.get("steps"):
        return None
    red = of(rec)
    if red is None or not is_traced_program(red):
        return None
    s = sum(red.scope_s.get(k, 0.0) for k in scopes)
    return 1000.0 * s / rec.traced["steps"] if s > 0 else None


def serve_span_ms(rec, names, *, idle: bool, per: str) -> float | None:
    """Host ms (or, with ``idle``, device idle ms) inside the program spans
    ``names``, per prompt position fed (``per="position"``) or per generated
    step (``per="step"``) of the traced requests.  None where the spans are
    absent or do not count one ``prefill`` a request and one of each decode
    span a generated step, or the decode program's runs match neither
    layout of the requests."""
    from chipbench.metrics.serve_decode_roofline import decode_calls

    if rec.kind != "serve":
        return None
    red = of(rec)
    reqs = rec.traced.get("requests", [])
    if red is None or not reqs or decode_calls(rec) is None:
        return None
    steps = sum(new for _, _, new in reqs)
    if red.span_n.get("prefill") != len(reqs) or any(
            red.span_n.get(n) != steps for n in DECODE_SPANS):
        return None
    count = sum(plen for _, plen, _ in reqs) if per == "position" else steps
    got = red.span_idle_s if idle else red.span_s
    return 1000.0 * sum(got.get(n, 0.0) for n in names) / count


def main(argv=None) -> int:
    """Print, as JSON, the program reduction of a cell's last traced run
    (``chipbench/run.py --trace 1`` leaves its trace in place), with the
    scopes of the train step or of the decode step.  Runs only on the chip
    that ran the cell: the scopes come from the step compiled again here,
    and a program compiled for another backend names its ops otherwise.
    Refuses (exit 1) where over 1% of the busy time lies in ops that the
    recompiled program does not have.

        python3 -m chipbench.program_trace --workload <name>
    """
    import argparse
    import json
    import sys
    from dataclasses import asdict

    import jax

    from chipbench import bench, generator
    from chipbench.cell import TRACE_DIR

    ap = argparse.ArgumentParser(description=main.__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    args = ap.parse_args(argv)
    if jax.default_backend() != "tpu":
        print(f"program_trace: JAX found no TPU (backend {jax.default_backend()!r}); "
              "the scopes must come from the program compiled for the chip", file=sys.stderr)
        return 2
    sys.path.insert(0, str(bench.ROOT / "src"))
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    cell = bench.workload(args.workload)
    rec = bench.Record(workload=cell["name"], config=bench.config(cell["config"]),
                       mix=generator.load(cell["traffic"]), device_kind="", chips=cell["chips"])
    train = rec.kind == "train"
    red = reduce_program(find_xplane(TRACE_DIR / rec.workload),
                         hlo_ops(train_hlo(rec) if train else decode_hlo(rec)),
                         TRAIN_MODULE if train else "jit_decode_step")
    if not is_traced_program(red):
        print(f"program_trace: {red.unmatched_s:.6f} s of {red.busy_s:.6f} s busy lies in ops "
              "the recompiled program does not have; it is not the program traced",
              file=sys.stderr)
        return 1
    print(json.dumps(asdict(red)))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
