"""The reduction of the program's stage spans (``program_spans.py``).

On synthetic intervals: self time is a span's duration less its nested
spans, per thread line and clipped to the window; device-idle time is
split by the innermost span of the main thread.  On ``small.xplane.pb``,
whose program wrote no stage spans, the reduction is None and every
reader returns None.  ``testdata/program_spans.xplane.pb`` was recorded
on a TPU v5e by ``record_program_spans.py``: two factorizations of a
31 x 31 grid through ``Session.execute`` inside ``bench.window``, with a
20 ms host sleep between them outside any span of the program.

    python -m pytest benchmarks/chip/test_program_spans.py   # runs on a CPU
"""
import runpy
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
SPANS = runpy.run_path(str(HERE / "program_spans.py"))
CHIP_TRACE = HERE / "testdata" / "program_spans.xplane.pb"
NO_SPANS_TRACE = HERE / "testdata" / "small.xplane.pb"
READERS = (
    "executor.gather_s", "executor.extend_add_s", "executor.pack_s",
    "executor.transfer_s", "executor.bookkeeping_s",
    "device_idle.factor.unattributed",
)


class FakeRun:
    def __init__(self, trace_dir, factorizations=2):
        self.trace_dir = trace_dir
        self.layer = {"factorizations": factorizations}


def reduce_synthetic(monkeypatch, window, lines, main, ops):
    monkeypatch.setitem(
        SPANS["reduce"].__globals__, "read_trace",
        lambda path: (window, lines, main, ops),
    )
    return SPANS["reduce"]("synthetic")


def test_segments_split_time_by_the_innermost_span():
    spans = [(0, 100, "P"), (10, 30, "C1"), (15, 20, "G"), (40, 100, "C2")]
    assert SPANS["segments"](spans) == [
        (0, 10, "P"), (10, 15, "C1"), (15, 20, "G"), (20, 30, "C1"),
        (30, 40, "P"), (40, 100, "C2"),
    ]


def test_segments_leave_out_time_in_no_span():
    assert SPANS["segments"]([(5, 8, "A"), (20, 30, "B")]) == [
        (5, 8, "A"), (20, 30, "B"),
    ]


def test_overlap_by_name_splits_intervals_and_keeps_the_rest():
    segs = [(0, 10, "A"), (10, 20, "B"), (30, 40, "A")]
    got = SPANS["overlap_by_name"]([(5, 15), (18, 35), (50, 60)], segs)
    assert got == {"A": 5 + 5, "B": 5 + 2, "no program span": 10 + 10}


def test_self_time_per_line_clipped_to_the_window(monkeypatch):
    # main line: session.execute 100..900 holds executor.run 150..850,
    # which holds executor.gather 200..300 and executor.wait 300..800;
    # a worker line: executor.dispatch 250..750 holds executor.h2d
    # 260..300 and executor.kernel 300..700; the window is 120..880
    main = [
        (100, 900, "session.execute"), (150, 850, "executor.run"),
        (200, 300, "executor.gather"), (300, 800, "executor.wait"),
    ]
    worker = [
        (250, 750, "executor.dispatch"), (260, 300, "executor.h2d"),
        (300, 700, "executor.kernel"),
    ]
    red = reduce_synthetic(
        monkeypatch, (120, 880), [[], main, worker], 1,
        {"/device:TPU:0": [(320, 690)]},
    )
    ns = {k: round(v * 1e9) for k, v in red["self_s"].items()}
    assert ns == {
        "session.execute": (150 - 120) + (880 - 850),
        "executor.run": (200 - 150) + (850 - 800),
        "executor.gather": 100,
        "executor.wait": 500,
        "executor.dispatch": 10 + 50,
        "executor.h2d": 40,
        "executor.kernel": 400,
    }
    assert red["count"]["executor.gather"] == 1
    # idle: 120..320 and 690..880; the main thread is in a program span
    # all the while, so nothing is unattributed
    assert round(red["idle_s"] * 1e9) == 200 + 190
    by = {k: round(v * 1e9) for k, v in red["idle_by_stage"].items()}
    assert by == {
        "session.execute": 30 + 30, "executor.run": 50 + 50,
        "executor.gather": 100, "executor.wait": 20 + 110,
        "no program span": 0,
    }
    assert red["idle_unattributed_s"] == 0
    assert red["idle_gaps"][0][0] == "executor.gather"  # midpoint 220


def test_idle_outside_every_program_span_is_unattributed(monkeypatch):
    main = [(0, 400, "session.execute"), (600, 1000, "session.execute")]
    red = reduce_synthetic(
        monkeypatch, (0, 1000), [main], 0,
        {"/device:TPU:0": [(100, 300), (700, 900)]},
    )
    assert round(red["idle_s"] * 1e9) == 600
    assert round(red["idle_unattributed_s"] * 1e9) == 200
    assert red["idle_gaps"][0] == ["no program span", pytest.approx(400e-9)]
    run = FakeRun(None)
    run.layer["program_spans"] = red
    assert SPANS["unattributed_idle_pct"](run) == pytest.approx(100 * 200 / 600)
    assert SPANS["stage_seconds"](run, ("session.execute",)) == \
        pytest.approx(800e-9 / 2)
    assert SPANS["stage_seconds"](run, ("executor.gather",)) is None


@pytest.mark.parametrize("name", READERS)
def test_readers_return_none_on_a_trace_without_program_spans(name, tmp_path):
    pytest.importorskip("jax")
    profile = tmp_path / "plugins" / "profile" / "1"
    profile.mkdir(parents=True)
    (profile / "t.xplane.pb").write_bytes(NO_SPANS_TRACE.read_bytes())
    reader = runpy.run_path(str(HERE / "metrics" / f"{name}.py"))
    run = FakeRun(tmp_path)
    assert reader["read"](run) is None
    assert run.layer["program_spans"] is None


# ----------------------------------------------------------------------
# The trace recorded on the chip
# ----------------------------------------------------------------------
# host line 14 is the main thread: bench.window 48,238,236 ns for
# 400,060,576 ns; session.execute 48,257,546 .. 237,878,860 and
# 258,455,828 .. 448,148,663, each holding one executor.run
# (188,996,544 and 189,028,185 ns long); between the two, the 20 ms
# sleep.  Each factorization ran 26 dispatches.
MAIN_LINE = 14
OUTSIDE_PROGRAM_NS = (
    (48257546 - 48238236) + (258455828 - 237878860)
    + (48238236 + 400060576 - 448148663)
)
PER_DISPATCH = (
    "executor.schedule", "executor.gather", "executor.extend_add",
    "executor.pack", "executor.wait", "executor.unpack",
    "executor.dispatch", "executor.h2d", "executor.kernel", "executor.d2h",
)


@pytest.fixture(scope="module")
def chip():
    pytest.importorskip("jax")
    window, lines, main, ops = SPANS["read_trace"](CHIP_TRACE)
    return {"red": SPANS["reduce"](CHIP_TRACE), "window": window,
            "lines": lines, "main": main, "ops": ops}


def brute_self_ns(spans) -> dict:
    """Self time by the definition: duration less the direct children's."""
    out = {}
    for a, b, name in spans:
        kids = [
            (c, d) for c, d, n in spans
            if (c, d, n) != (a, b, name) and a <= c and d <= b
            and not any(
                (x, y, m) not in ((a, b, name), (c, d, n))
                and a <= x <= c and d <= y <= b
                for x, y, m in spans
            )
        ]
        out[name] = out.get(name, 0) + (b - a) - sum(d - c for c, d in kids)
    return out


def test_chip_trace_counts_every_stage(chip):
    count = chip["red"]["count"]
    for name in ("session.execute", "executor.run", "executor.prologue",
                 "executor.epilogue"):
        assert count[name] == 2, name
    for name in PER_DISPATCH:
        assert count[name] == 2 * 26, name


def test_chip_trace_main_and_worker_lines(chip):
    assert chip["main"] == MAIN_LINE
    on = {i: {n for *_, n in spans} for i, spans in enumerate(chip["lines"])}
    assert {"executor.gather", "executor.extend_add", "executor.pack",
            "executor.unpack", "executor.wait"} <= on[MAIN_LINE]
    workers = [i for i, names in on.items() if "executor.dispatch" in names]
    assert workers and MAIN_LINE not in workers
    for i in workers:
        assert {"executor.h2d", "executor.kernel", "executor.d2h"} <= on[i]


def test_chip_trace_self_times_match_the_definition(chip):
    lo, hi = chip["window"]
    want = {}
    for spans in chip["lines"]:
        for name, ns in brute_self_ns(SPANS["clip"](spans, lo, hi)).items():
            want[name] = want.get(name, 0) + ns
    got = chip["red"]["self_s"]
    assert set(got) == set(want)
    for name, ns in want.items():
        assert got[name] == pytest.approx(ns * 1e-9, abs=1e-12), name
    # session.execute less its executor.run, twice
    assert round(got["session.execute"] * 1e9) == \
        (189621314 - 188996544) + (189692835 - 189028185)


def test_chip_trace_idle_attribution(chip):
    red = chip["red"]
    trace = SPANS["trace"]
    busy = trace.union(
        [r for r in chip["ops"]["/device:TPU:0"]], *chip["window"]
    )
    lo, hi = chip["window"]
    assert round(red["idle_s"] * 1e9) == (hi - lo) - sum(b - a for a, b in busy)
    # the device is idle all the while the main thread is outside the
    # program: before, between (the sleep) and after the two executes
    assert round(red["idle_unattributed_s"] * 1e9) == OUTSIDE_PROGRAM_NS
    assert sum(red["idle_by_stage"].values()) == pytest.approx(red["idle_s"])
    assert red["idle_gaps"][0][0] == "no program span"  # the sleep
    assert red["idle_gaps"][0][1] > 0.02


def test_chip_trace_readers(chip, tmp_path):
    profile = tmp_path / "plugins" / "profile" / "1"
    profile.mkdir(parents=True)
    (profile / "t.xplane.pb").write_bytes(CHIP_TRACE.read_bytes())
    self_s, red = chip["red"]["self_s"], chip["red"]
    want = {
        "executor.gather_s": self_s["executor.gather"] / 2,
        "executor.extend_add_s": self_s["executor.extend_add"] / 2,
        "executor.pack_s": (self_s["executor.pack"] + self_s["executor.unpack"]) / 2,
        "executor.transfer_s": (self_s["executor.h2d"] + self_s["executor.d2h"]) / 2,
        "executor.bookkeeping_s": sum(
            self_s[n] for n in ("session.execute", "executor.run",
                                "executor.prologue", "executor.schedule",
                                "executor.epilogue")
        ) / 2,
        "device_idle.factor.unattributed":
            100 * red["idle_unattributed_s"] / red["idle_s"],
    }
    run = FakeRun(tmp_path)
    for name, value in want.items():
        reader = runpy.run_path(str(HERE / "metrics" / f"{name}.py"))
        assert reader["read"](run) == pytest.approx(value), name
