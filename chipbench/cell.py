"""What every kind of cell shares: the run's context, the program's
configuration built from the benchmark's files, spans and tracing."""
from __future__ import annotations

import dataclasses
import shutil
import time
from contextlib import contextmanager
from dataclasses import dataclass
from pathlib import Path

import jax

from chipbench.bench import ROOT, Record

TRACE_DIR = ROOT / "experiments" / "runs" / "chipbench"


@dataclass
class Context:
    rec: Record
    seed: int
    seconds: float
    trace: bool
    devices: list
    t_start: float               # process start, host clock
    fault: str | None = None     # a fault planted in the timed path (tests, readings)
    log: object = print


def program_arch(c: dict, fam, mix: dict, dtype: str | None = None):
    """The program's ArchConfig for configuration ``c``: the registered
    architecture with every size the benchmark's file states, and the job's
    training recipe from the traffic mix."""
    from repro.configs import get_arch

    arch = get_arch(c["repro_arch"])
    fields = dict(fam.program_fields(c))
    if dtype:
        fields["dtype"] = dtype
    model = arch.model.replace(**fields)
    train = arch.train
    if "optimizer" in mix:
        o = mix["optimizer"]
        train = dataclasses.replace(
            train, optimizer=o["name"], learning_rate=o["lr"], beta1=o["beta1"],
            beta2=o["beta2"], weight_decay=o["weight_decay"],
            grad_clip=o["grad_clip"], remat=mix["remat"],
            comm_pattern=mix["comm_pattern"], micro_batches=1)
    return arch.replace(model=model, train=train)


@contextmanager
def span(name: str):
    """A host span in the profiler's trace (free when no trace runs)."""
    with jax.profiler.TraceAnnotation(name):
        yield


@contextmanager
def traced(workload: str):
    """Profile the enclosed block into a fresh directory of this workload's;
    yields the directory."""
    out = TRACE_DIR / workload
    shutil.rmtree(out, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(out), profiler_options=opts):
        yield out


def memory_peak(devices) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def now() -> float:
    return time.perf_counter()
