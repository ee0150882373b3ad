"""Reduce a profiler trace of the measured window to device metrics.

Reads the ``.xplane.pb`` that ``jax.profiler`` writes with nothing but
``jax.profiler.ProfileData``.  From the device planes (``/device:TPU:<k>``)
it takes the operations of the ``XLA Ops`` line; from the host plane the
benchmark's own spans (``bench.*``, written with ``TraceAnnotation``).
The window is the host span ``bench.window``.  The device clock of a
v5e trace can run about a millisecond off the host's (``test_trace.py``),
which a window of tens of seconds loses at most at its two ends.

* ``busy_s``: the union of the device's operation intervals inside the
  window, averaged over the chips that ran anything;
* ``window_s``: the window's length;
* ``ops``: per operation, its device seconds, and per kernel path in
  ``KERNELS`` its device seconds (``kernels``); an operation is named by
  its jitted function, its HLO name and its output shape
  (``jit__batched_front_factor %vmap__.1 f32[32,256,256]``);
* ``device_ops``: the 10 operations that took most device time;
* ``idle_gaps``: the 10 longest gaps between device operations inside the
  window, each named by the innermost benchmark span the host was in at
  the gap's midpoint.

The program's kernels carry no ``name=`` yet.  On a v5e a Pallas kernel
shows in ``XLA Ops`` as the HLO text of its custom call, with
``custom_call_target="tpu_custom_call"``, inside the ``XLA Modules`` event
of the jitted function that launched it (``jit__batched_front_factor(<hash>)``).
So a kernel is matched by that target and by the jitted function's name
(``KERNELS``), read off a trace recorded on the chip
(``testdata/``, ``record_trace.py``).
"""
from __future__ import annotations

import bisect
import glob
import re
from collections import defaultdict
from pathlib import Path

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
PALLAS_CALL = 'custom_call_target="tpu_custom_call"'
WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."

# kernel path -> the jitted functions whose Pallas calls are its kernels:
# the whole-front-in-VMEM kernel is batched_front_factor's one call; the
# large-front path is partial_cholesky's panel_factor and syrk_downdate
KERNELS = {
    "front_factor_vmem": ("jit__batched_front_factor",),
    "large_front": ("jit__partial_cholesky_impl",),
}


def latest_xplane(trace_dir) -> Path:
    paths = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return Path(paths[-1])


def events(path):
    """(device planes' ops, host spans) of one trace file.

    ops: {plane name: [(start_ns, end_ns, name, kernel)]}, where kernel
    is the ``KERNELS`` path of a Pallas call, else None.
    spans: [(start_ns, end_ns, name)] of the host's ``bench.*`` spans."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    ops, spans = {}, []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = {line.name: list(line.events) for line in plane.lines}
            modules = sorted(
                (int(e.start_ns), int(e.start_ns) + int(e.duration_ns), e.name)
                for e in lines.get(MODULES_LINE, ())
            )
            rows = []
            for e in lines.get(OPS_LINE, ()):
                t0 = int(e.start_ns)
                module = module_at(modules, t0)
                kernel = kernel_of(module) if PALLAS_CALL in e.name else None
                rows.append((t0, t0 + int(e.duration_ns),
                             op_label(module, e.name), kernel))
            ops[plane.name] = rows
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        t0 = int(e.start_ns)
                        spans.append((t0, t0 + int(e.duration_ns), e.name))
    return ops, spans


def module_at(modules, t: int) -> str:
    """Name of the module event (sorted by start) that holds time t."""
    i = bisect.bisect_right(modules, (t, float("inf"), "")) - 1
    if i >= 0 and modules[i][0] <= t <= modules[i][1]:
        return modules[i][2]
    return ""


def op_label(module: str, hlo: str) -> str:
    """``<jitted function> <HLO name> <output shape>`` of an op's HLO text
    (``%vmap__.1 = f32[4,128,128]{2,1,0:T(8,128)} custom-call(...)``)."""
    lhs, _, rhs = hlo.partition(" = ")
    shape = rhs.split("{", 1)[0].split(" ", 1)[0] if rhs else ""
    return " ".join(x for x in (module.split("(", 1)[0], lhs, shape) if x)


def kernel_of(module: str):
    fn = module.split("(", 1)[0]
    for kernel, fns in KERNELS.items():
        if fn in fns:
            return kernel
    return None


def union(intervals, lo: int, hi: int) -> list:
    """Merged intervals clipped to [lo, hi]."""
    out = []
    for a, b in sorted(intervals):
        a, b = max(a, lo), min(b, hi)
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def innermost(spans, t: int) -> str:
    best = None
    for a, b, name in spans:
        if a <= t <= b and (best is None or b - a < best[1] - best[0]):
            best = (a, b, name)
    return best[2] if best else "no benchmark span"


def reduce(path) -> dict:
    ops, spans = events(path)
    windows = [(a, b) for a, b, n in spans if n == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"no {WINDOW_SPAN} span in {path}")
    lo, hi = windows[0]
    busy, gaps = [], []
    per_op = defaultdict(float)
    per_kernel = defaultdict(float)
    for rows in ops.values():
        inside = [(a, b) for a, b, *_ in rows if b > lo and a < hi]
        if not inside:
            continue
        merged = union(inside, lo, hi)
        busy.append(sum(b - a for a, b in merged))
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                gaps.append((b - a, innermost(spans, (a + b) // 2)))
        for a, b, name, kernel in rows:
            d = max(0, min(b, hi) - max(a, lo))
            per_op[name] += d * 1e-9
            if kernel is not None:
                per_kernel[kernel] += d * 1e-9
    gaps.sort(reverse=True)
    top = sorted(per_op.items(), key=lambda kv: -kv[1])[:10]
    return {
        "busy_s": (sum(busy) / len(busy)) * 1e-9 if busy else 0.0,
        "window_s": (hi - lo) * 1e-9,
        "chips_busy": len(busy),
        "ops": dict(per_op),
        "kernels": dict(per_kernel),
        "device_ops": [[name, s] for name, s in top],
        "idle_gaps": [[name, d * 1e-9] for d, name in gaps[:10]],
    }


def reduce_run(trace_dir) -> dict:
    """The reduction of the newest trace under ``trace_dir``."""
    return reduce(latest_xplane(trace_dir))
