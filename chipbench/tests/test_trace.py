"""The trace reduction on a small trace recorded on one v5e chip: three
steps of a jitted matmul-and-reduce, each inside the harness's spans."""
from pathlib import Path

import pytest

from chipbench import trace
from chipbench.train_cell import SPANS

DATA = Path(__file__).resolve().parents[1] / "testdata" / "spans.xplane.pb"


@pytest.fixture(scope="module")
def red():
    return trace.reduce_trace(DATA, SPANS)


def test_window_busy_and_programs(red):
    assert red.window_s == pytest.approx(0.102482097)
    # three conversions of 2771 ns, three copies and fusions of the step
    assert red.busy_s == pytest.approx(24315e-9)
    assert red.module_calls == {"jit_convert_element_type": 3, "jit__lambda": 3}
    assert red.module_s["jit__lambda"] == pytest.approx((5262 + 5243 + 5520) * 1e-9)


def test_top_ops_and_idle_gaps_named_by_host_spans(red):
    assert red.top_ops[0][0] == "convolution_reduce_fusion"
    assert sum(s for _, s in red.idle_gaps) == pytest.approx(red.window_s - red.busy_s)
    # the first batch transfer compiles the conversion: most of the window
    assert red.idle_gaps[0][0] == "batch transfer"
    assert red.idle_gaps[0][1] > 0.08


def test_interval_arithmetic():
    a = trace._union([(0, 2), (1, 3), (5, 6)])
    assert a == [[0, 3], [5, 6]] and trace._length(a) == 4
    assert trace.op_name("%fusion.1 = f32[2] fusion(x)") == "fusion.1"
    assert trace.module_name("jit_train_step(123)") == "jit_train_step"
