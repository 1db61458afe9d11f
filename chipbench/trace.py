"""Reduction of a profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics read.

A device plane (``/device:TPU:<n>``) has an ``XLA Modules`` line, one event
per run of a jitted program, and an ``XLA Ops`` line, one event per
operation; a loop or call operation spans the operations of its body.  Host
planes carry the harness's own ``TraceAnnotation`` spans, on the same clock.
Everything is clipped to the harness's ``window`` span.
"""
from __future__ import annotations

import glob
import re
from dataclasses import dataclass, field
from pathlib import Path

WINDOW_SPAN = "window"
_CONTAINER = re.compile(r"^(while|conditional|call)\b")


@dataclass
class Reduction:
    window_s: float                      # length of the window span
    busy_s: float                        # device busy, mean over chips
    module_s: dict                       # jitted program -> device seconds (mean over chips)
    module_calls: dict                   # jitted program -> runs (first chip)
    top_ops: list = field(default_factory=list)    # [[op, seconds]], first chip
    idle_gaps: list = field(default_factory=list)  # [[host span, seconds]], first chip


def find_xplane(log_dir: str | Path) -> Path:
    files = sorted(glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return Path(files[-1])


def _union(intervals):
    """Sorted, merged copy of [(start, end)]."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _length(merged) -> float:
    return sum(e - s for s, e in merged)


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def op_name(event_name: str) -> str:
    """'%fusion.12 = bf16[...] fusion(...)' -> 'fusion.12'."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def module_name(event_name: str) -> str:
    """'jit_train_step(9859192325133382770)' -> 'jit_train_step'."""
    return event_name.split("(", 1)[0]


def _line(plane, name):
    for line in plane.lines:
        if line.name == name:
            return list(line.events)
    return []


def _host_spans(planes, names) -> list:
    """[(start_ns, end_ns, name)] of the harness's spans on the host planes."""
    out = []
    for plane in planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in names:
                    out.append((ev.start_ns, ev.start_ns + ev.duration_ns, ev.name))
    return out


def reduce_trace(path: str | Path, span_names=(), top: int = 10) -> Reduction:
    """Reduce the trace at ``path`` over its window span.  ``span_names`` are
    the harness's host spans that name the device's idle gaps."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    planes = list(data.planes)
    windows = _host_spans(planes, {WINDOW_SPAN})
    if not windows:
        raise ValueError(f"trace {path} has no {WINDOW_SPAN!r} span")
    lo, hi = min(w[0] for w in windows), max(w[1] for w in windows)
    spans = _host_spans(planes, set(span_names))
    devices = sorted((p for p in planes if re.match(r"^/device:TPU:\d+$", p.name)),
                     key=lambda p: int(p.name.rsplit(":", 1)[1]))
    if not devices:
        raise ValueError(f"trace {path} has no TPU device plane")

    busy, module_s = [], {}
    first = None
    for n, plane in enumerate(devices):
        ops = [(ev.start_ns, ev.start_ns + ev.duration_ns, op_name(ev.name))
               for ev in _line(plane, "XLA Ops")]
        ops = [(s, e, name) for s, e, name in ops if e > lo and s < hi]
        merged = _union(_clip([(s, e) for s, e, _ in ops], lo, hi))
        busy.append(_length(merged))
        calls = {}
        for ev in _line(plane, "XLA Modules"):
            s, e = ev.start_ns, ev.start_ns + ev.duration_ns
            if e > lo and s < hi:
                m = module_name(ev.name)
                module_s[m] = module_s.get(m, 0.0) + (min(e, hi) - max(s, lo)) / 1e9
                calls[m] = calls.get(m, 0) + 1
        if first is None:
            first = (ops, merged, calls)

    ops, merged, calls = first
    per_op = {}
    for s, e, name in ops:
        if not _CONTAINER.match(name):
            per_op[name] = per_op.get(name, 0.0) + (min(e, hi) - max(s, lo)) / 1e9
    top_ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]

    gaps, prev = {}, lo
    for s, e in merged + [[hi, hi]]:
        if s > prev:
            gaps_name = _cause(prev, s, spans)
            gaps[gaps_name] = gaps.get(gaps_name, 0.0) + (s - prev) / 1e9
        prev = max(prev, e)
    idle = sorted(gaps.items(), key=lambda kv: -kv[1])[:top]

    n = len(devices)
    return Reduction(window_s=(hi - lo) / 1e9, busy_s=sum(busy) / n / 1e9,
                     module_s={k: v / n for k, v in module_s.items()},
                     module_calls=calls,
                     top_ops=[[k, v] for k, v in top_ops],
                     idle_gaps=[[k, v] for k, v in idle])


def _cause(s, e, spans) -> str:
    """The host span that overlaps the gap [s, e) most, else 'other'."""
    best, name = 0.0, "other"
    for a, b, span in spans:
        o = min(b, e) - max(a, s)
        if o > best:
            best, name = o, span
    return name
