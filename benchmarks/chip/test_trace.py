"""The trace reduction against numbers worked out by hand.

``testdata/small.xplane.pb`` was recorded on a TPU v5e by
``record_trace.py``: inside the host
span ``bench.window`` it ran a batched whole-front kernel on
(4, 128, 128), slept 20 ms, then ran one on (2, 256, 256) and one
1200-order front with 600 pivots through the large-front path.  The
device clock of that trace runs about 0.8 ms ahead of the host's: the
first batched kernel (41,984,563 ns, 99,099 ns long) ends before the
window span opens (42,739,555 ns), so it lies outside the window and
counts nowhere.  Its other 42 operations follow one another on the one
TensorCore without overlap; their durations (ns), from the trace:

    92818 (the (2, 256, 256) kernel), 6410, 2, 3, 3, 1068, 7, 8853, 8852,
    3, 320, 455, 2, 3, 3, 3, 0, 3286, 7093, 6396, 557,
    1382172 (panel), 441, 395, 361, 20437 (SYRK), 390, 68,
    90558 (panel), 66, 269, 55, 7695 (SYRK), 270, 831, 503, 849, 29,
    6443, 2516, 2, 4896

    python -m pytest benchmarks/chip/test_trace.py      # runs on a CPU
"""
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import trace as reduction  # noqa: E402

TRACE = HERE / "testdata" / "small.xplane.pb"
OPS_IN_WINDOW_NS = [
    92818, 6410, 2, 3, 3, 1068, 7, 8853, 8852,
    3, 320, 455, 2, 3, 3, 3, 0, 3286, 7093, 6396, 557,
    1382172, 441, 395, 361, 20437, 390, 68,
    90558, 66, 269, 55, 7695, 270, 831, 503, 849, 29,
    6443, 2516, 2, 4896,
]
WINDOW = (42739555, 42739555 + 24970238)


@pytest.fixture(scope="module")
def reduced():
    return reduction.reduce(TRACE)


def test_window_is_the_benchmark_span(reduced):
    assert reduced["window_s"] == pytest.approx(24970238e-9, abs=1e-15)


def test_busy_is_the_union_of_ops_in_the_window(reduced):
    assert sum(OPS_IN_WINDOW_NS) == 1655383
    assert reduced["chips_busy"] == 1
    assert reduced["busy_s"] == pytest.approx(1655383e-9, abs=1e-15)


def test_kernel_time_per_path(reduced):
    # whole-front kernel: only the (2, 256, 256) call is in the window
    assert reduced["kernels"]["front_factor_vmem"] == pytest.approx(92818e-9)
    # large front: 2 panel_factor and 2 syrk_downdate Pallas calls
    large = 1382172 + 20437 + 90558 + 7695
    assert reduced["kernels"]["large_front"] == pytest.approx(large * 1e-9)


def test_longest_idle_gaps_and_what_the_host_was_doing(reduced):
    gaps = reduced["idle_gaps"]
    # window start -> the (2, 256, 256) kernel: the 20 ms sleep, outside
    # any bench.factorize span
    assert gaps[0] == ["bench.window", pytest.approx((63107001 - WINDOW[0]) * 1e-9)]
    # last op (copy-done, 65945046 + 4896) -> window end, inside the
    # second bench.factorize span (63990733 .. 67706153)
    assert gaps[1] == ["bench.factorize",
                       pytest.approx((WINDOW[1] - 65945046 - 4896) * 1e-9)]
    # the (2, 256, 256) kernel's end -> the large front's first op
    assert gaps[2] == ["bench.window",
                       pytest.approx((64387336 - 63107001 - 92818) * 1e-9)]


def test_device_ops_name_the_function_op_and_shape(reduced):
    name, seconds = reduced["device_ops"][0]
    assert name == "jit__partial_cholesky_impl %_partial_cholesky_impl.4 f32[1280,512]"
    assert seconds == pytest.approx(1382172e-9)
    assert len(reduced["device_ops"]) == 10


def test_union_merges_overlaps_and_clips():
    assert reduction.union([(5, 9), (0, 3), (2, 4), (8, 12)], 1, 10) == \
        [[1, 4], [5, 10]]
