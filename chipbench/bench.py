"""What a run is made of, found by the names in ``BENCHMARK.json``: the cell,
its configuration file and family reference, its traffic mix, the per-cell
limits of the comparison with the reference, and one reader per metric."""
from __future__ import annotations

import importlib
import json
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any

from chipbench.reference import family

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


def spec(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def workload(name: str, root: Path = ROOT) -> dict:
    for w in spec(root)["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def config(name: str, root: Path = ROOT) -> dict:
    for c in spec(root)["configs"]:
        if c["name"] == name:
            return json.loads((root / c["file"]).read_text())
    raise KeyError(f"no config {name!r} in BENCHMARK.json")


def limits(name: str) -> dict:
    """{number: limit} of the cell's comparison with the reference; a number
    with no limit (no reading separates it) is not compared."""
    data = json.loads((HERE / "limits" / f"{name}.json").read_text())
    return {k: v["limit"] for k, v in data.items() if v["limit"] is not None}


def metrics_of(name: str, traced: bool, root: Path = ROOT) -> list[dict]:
    """The metrics a run of workload ``name`` reports: its end-to-end metrics
    untraced, its per-layer metrics traced."""
    s = spec(root)
    group = s["per_layer"] if traced else s["end_to_end"]
    return [m for m in group if name in m.get("workloads", [name])]


def read_metric(metric: dict, rec: "Record"):
    """Value of one metric from its reader ``metrics/<name>.py``, or None
    where the reader finds nothing to read."""
    mod = importlib.import_module(f"chipbench.metrics.{metric['name']}")
    return mod.read(rec)


@dataclass
class Record:
    """Everything a run measured, for the metric readers."""
    workload: str
    config: dict
    mix: dict
    device_kind: str
    chips: int
    setup_s: float = 0.0
    window_s: float = 0.0        # host clock: the measured window
    work_tokens: int = 0         # tokens trained, or generated, in the window
    traced: dict = field(default_factory=dict)  # what the traced window ran
    trace: Any = None            # trace.Reduction of the traced window

    @property
    def family(self):
        return family(self.config["family"])

    @property
    def kind(self) -> str:
        return self.mix["kind"]

