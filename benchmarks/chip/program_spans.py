"""Reduce the program's own host spans in a profiler trace of the window.

The program names each stage of its host work with ``obs.stage``
(``session.execute``, ``executor.run``, ``executor.gather``, ...,
``src/repro/obs/events.py``): a ``jax.profiler.TraceAnnotation``, so the
stage lies on the trace's host plane, on the clock of the device's
operations.  This reads the same ``.xplane.pb`` as ``trace.py``, with
``jax.profiler.ProfileData``, and keeps the program's spans (names that
start with ``session.`` or ``executor.``) per thread line of the host
plane, clipped to the benchmark's ``bench.window`` span.

* ``self_s``: per stage, its self time: its duration less the part that
  stage spans nested in it cover on the same line, summed over lines;
* ``count``: per stage, the spans that reach into the window;
* ``idle_s``: the device's idle time in the window (the window less the
  union of the device's operations, summed over the chips that ran any);
* ``idle_unattributed_s``: the part of ``idle_s`` in which the main
  thread (the line of ``bench.window``) is in no program span;
* ``idle_by_stage``: ``idle_s`` split by the innermost program span the
  main thread is in;
* ``idle_gaps``: the 10 longest idle gaps, each named by the innermost
  program span the main thread is in at its midpoint.

A trace without program spans reduces to ``None``: a program that has no
stage spans is read as reporting nothing, not zero.
"""
from __future__ import annotations

import importlib.util
from collections import defaultdict
from pathlib import Path

PREFIXES = ("session.", "executor.")
NO_SPAN = "no program span"


def _load_trace():
    path = Path(__file__).resolve().parent / "trace.py"
    spec = importlib.util.spec_from_file_location("chipbench_trace_spans", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


trace = _load_trace()


def clip(spans, lo: int, hi: int) -> list:
    return [(max(a, lo), min(b, hi), n) for a, b, n in spans if min(b, hi) > max(a, lo)]


def segments(spans) -> list:
    """One thread's time split by its innermost open span: [(a, b, name)]
    in time order.  The spans nest, as one thread's annotations do; time
    in no span is left out."""
    out, stack, t = [], [], 0

    def emit(a, b, name):
        if b > a:
            out.append((a, b, name))

    for a, b, name in sorted(spans, key=lambda s: (s[0], -s[1])):
        while stack and stack[-1][0] <= a:
            end, inner = stack.pop()
            emit(t, end, inner)
            t = end
        if stack:
            emit(t, a, stack[-1][1])
        t = a
        stack.append((b, name))
    while stack:
        end, inner = stack.pop()
        emit(t, end, inner)
        t = end
    return out


def overlap_by_name(intervals, segs) -> dict:
    """Length of ``intervals`` (sorted, disjoint) inside each name's
    segments (sorted, disjoint), and outside all of them under NO_SPAN."""
    out = defaultdict(int)
    j = 0
    for a, b in intervals:
        covered = 0
        while j < len(segs) and segs[j][1] <= a:
            j += 1
        k = j
        while k < len(segs) and segs[k][0] < b:
            d = min(b, segs[k][1]) - max(a, segs[k][0])
            if d > 0:
                out[segs[k][2]] += d
                covered += d
            k += 1
        out[NO_SPAN] += (b - a) - covered
    return dict(out)


def innermost_at(segs, t: int) -> str:
    for a, b, name in segs:
        if a <= t < b:
            return name
    return NO_SPAN


def read_trace(path):
    """(window, host lines' program spans, main line index, device ops)
    of one trace file; window is None without a ``bench.window`` span."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    window, main, lines, ops = None, None, [], {}
    for plane in pd.planes:
        if trace.DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == trace.OPS_LINE:
                    ops[plane.name] = [
                        (int(e.start_ns), int(e.start_ns) + int(e.duration_ns))
                        for e in line.events
                    ]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans = []
                for e in line.events:
                    t0 = int(e.start_ns)
                    t1 = t0 + int(e.duration_ns)
                    if e.name == trace.WINDOW_SPAN:
                        window, main = (t0, t1), len(lines)
                    elif e.name.startswith(PREFIXES):
                        spans.append((t0, t1, e.name))
                lines.append(spans)
    return window, lines, main, ops


def reduce(path):
    """The reduction above of one trace file, or None where the window
    holds no program span."""
    window, lines, main, ops = read_trace(path)
    if window is None:
        raise ValueError(f"no {trace.WINDOW_SPAN} span in {path}")
    lo, hi = window
    segs = [segments(clip(spans, lo, hi)) for spans in lines]
    if not any(segs):
        return None
    self_ns, count = defaultdict(int), defaultdict(int)
    for spans, line_segs in zip(lines, segs):
        for a, b, name in line_segs:
            self_ns[name] += b - a
        for *_, name in clip(spans, lo, hi):
            count[name] += 1
    main_segs = segs[main]
    idle = []
    for rows in ops.values():
        busy = trace.union([r for r in rows if r[1] > lo and r[0] < hi], lo, hi)
        if not busy:
            continue
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        idle += [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]
    by_stage = overlap_by_name(sorted(idle), main_segs) if idle else {}
    idle_ns = sum(b - a for a, b in idle)
    gaps = sorted(idle, key=lambda iv: iv[0] - iv[1])[:10]
    return {
        "window_s": (hi - lo) * 1e-9,
        "self_s": {k: v * 1e-9 for k, v in self_ns.items()},
        "count": dict(count),
        "main_spans": len(main_segs),
        "idle_s": idle_ns * 1e-9,
        "idle_unattributed_s": by_stage.get(NO_SPAN, 0) * 1e-9,
        "idle_by_stage": {k: v * 1e-9 for k, v in by_stage.items()},
        "idle_gaps": [
            [innermost_at(main_segs, (a + b) // 2), (b - a) * 1e-9]
            for a, b in gaps
        ],
    }


def of_run(run):
    """The reduction of a run's trace, once per run; None without one."""
    if "program_spans" not in run.layer:
        try:
            path = trace.latest_xplane(run.trace_dir)
        except FileNotFoundError:
            run.layer["program_spans"] = None
        else:
            run.layer["program_spans"] = reduce(path)
    return run.layer["program_spans"]


def stage_seconds(run, names):
    """Summed self time of the stages ``names`` per factorization of the
    window; None where the trace holds none of them."""
    red = of_run(run)
    k = run.layer.get("factorizations")
    if red is None or not k or not any(n in red["self_s"] for n in names):
        return None
    return sum(red["self_s"].get(n, 0.0) for n in names) / k


def unattributed_idle_pct(run):
    """Share of the device's idle time in the window, in %, in which the
    main thread is in no program span; None without program spans on
    the main thread or without idle time."""
    red = of_run(run)
    if red is None or not red["main_spans"] or red["idle_s"] <= 0:
        return None
    return 100.0 * red["idle_unattributed_s"] / red["idle_s"]
