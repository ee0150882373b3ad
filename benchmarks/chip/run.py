#!/usr/bin/env python3
"""On-chip benchmark of the multifrontal factorization: one cell, one run.

    python benchmarks/chip/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>
    python benchmarks/chip/run.py --list

A cell is one entry of ``workloads`` in ``BENCHMARK.json`` at the root of
the checkout.  Everything a cell needs is found by name, so a later change
adds a cell, a configuration, a traffic mix or a per-layer metric by adding
files:

    configs/<file>          the deployment (``configs[].file``), whose
                            ``matrix.family`` names matrices/<family>.py
    traffic/<traffic>.json  the traffic mix, whose ``driver`` names
                            drivers/<driver>.py, the loop that drives it
    metrics/<metric>.py     one per-layer metric's reader: read(run) ->
                            number, or None where it finds nothing

A run checks for a TPU (there is no CPU path), turns on JAX's persistent
compilation cache in the checkout, lets the driver set up and warm the
cell's own shapes, measures for ``--seconds``, checks every answer of the
window against the plain reference (``reference.py``), and prints as the
last line of stdout one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics with ``--trace 0``,
its per-layer metrics with ``--trace 1``), ``device`` and last ``checks``,
each compared number beside its limit.  ``--trace 1`` records a profiler
trace of the window and reduces it with ``trace.py``.

``--list`` resolves every entry of ``BENCHMARK.json`` to its files without
touching JAX, and exits 1 if one is missing.
"""
from __future__ import annotations

import time

T_FIRST = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def process_age_s() -> float:
    """Seconds since this process started, from /proc (0 where absent)."""
    try:
        stat = Path("/proc/self/stat").read_text()
        start_ticks = float(stat.rsplit(")", 1)[1].split()[19])
        uptime = float(Path("/proc/uptime").read_text().split()[0])
        return max(uptime - start_ticks / os.sysconf("SC_CLK_TCK"), 0.0)
    except (OSError, ValueError, IndexError):
        return 0.0


# the process started this long before T_FIRST
T_PROCESS = T_FIRST - process_age_s()


def load_module(path: Path, name: str):
    """Import a benchmark file by path under a private module name."""
    spec = importlib.util.spec_from_file_location(f"chipbench_{name}", path)
    if spec is None or spec.loader is None:
        raise ImportError(f"cannot load {path}")
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


class Benchmark:
    """``BENCHMARK.json`` and the files it names, resolved by name."""

    def __init__(self, root: Path = ROOT) -> None:
        self.root = root
        self.spec = json.loads((root / "BENCHMARK.json").read_text())
        self.configs = {c["name"]: c for c in self.spec["configs"]}
        self.workloads = {w["name"]: w for w in self.spec["workloads"]}

    def config(self, name: str) -> dict:
        entry = self.configs[name]
        return json.loads((self.root / entry["file"]).read_text())

    def traffic(self, name: str) -> dict:
        return json.loads((HERE / "traffic" / f"{name}.json").read_text())

    def driver_path(self, traffic: dict) -> Path:
        return HERE / "drivers" / f"{traffic['driver']}.py"

    def family_path(self, cfg: dict) -> Path:
        return HERE / "matrices" / f"{cfg['matrix']['family']}.py"

    def metric_path(self, name: str) -> Path:
        return HERE / "metrics" / f"{name}.py"

    def cell_metrics(self, kind: str, cell: str) -> list:
        """The ``end_to_end`` or ``per_layer`` metrics a cell reports."""
        return [
            m for m in self.spec[kind]
            if cell in m.get("workloads", self.workloads)
        ]

    def resolve(self) -> list:
        """Every file an entry names, as (what, path); raises on the
        first one that is missing or does not parse."""
        out = []

        def need(what: str, path: Path) -> None:
            if not path.is_file():
                raise FileNotFoundError(f"{what}: {path} is missing")
            out.append((what, path))

        for c in self.spec["configs"]:
            need(f"config {c['name']}", self.root / c["file"])
            need(f"matrix family of {c['name']}",
                 self.family_path(self.config(c["name"])))
        for w in self.spec["workloads"]:
            if w["config"] not in self.configs:
                raise KeyError(f"workload {w['name']}: no config {w['config']}")
            need(f"traffic {w['traffic']}",
                 HERE / "traffic" / f"{w['traffic']}.json")
            need(f"driver of {w['traffic']}",
                 self.driver_path(self.traffic(w["traffic"])))
        for m in self.spec["per_layer"]:
            need(f"metric {m['name']}", self.metric_path(m["name"]))
        return out


class Run:
    """One run of one cell: what the driver sets up and measures, what
    the metric readers read, and what the checks compared."""

    def __init__(self, bench: Benchmark, cell: str, seed: int,
                 seconds: float, trace: bool) -> None:
        self.bench = bench
        self.cell = bench.workloads[cell]
        self.cfg = bench.config(self.cell["config"])
        self.traffic = bench.traffic(self.cell["traffic"])
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.tracing = bool(trace)
        self.chips = int(self.cell["chips"])
        self.devices: list = []
        self.setup: dict = {}  # set-up phase -> seconds
        self.e2e: dict = {}  # end-to-end metric -> value
        self.layer: dict = {}  # what per-layer readers read
        self.checks: list = []  # (name, value, limit, passed)
        self.attempted = 0
        self.failed = 0
        self.t_window = (math.nan, math.nan)
        self.compile_times: list = []
        self.trace_dir = HERE / "out" / "trace" / f"{cell}-{seed}"
        self.trace = None  # trace.py's reduction of the window
        self.family = load_module(bench.family_path(self.cfg), "family")
        self.deployment = load_module(HERE / "deployment.py", "deployment")
        self.reference = load_module(HERE / "reference.py", "reference")
        self.work = load_module(HERE / "work.py", "work")
        self.device_kind = ""

    # -- timers ----------------------------------------------------------
    @contextlib.contextmanager
    def timed(self, phase: str):
        t = time.perf_counter()
        try:
            yield
        finally:
            self.setup[phase] = time.perf_counter() - t

    def on_compile(self, event: str, duration: float, **_) -> None:
        if event == COMPILE_EVENT:
            self.compile_times.append(time.perf_counter())

    def compiles_in_window(self) -> int:
        t0, t1 = self.t_window
        return sum(1 for t in self.compile_times if t0 <= t <= t1)

    def span(self, name: str, **kw):
        """A host span in the profiler's trace (only when tracing)."""
        if not self.tracing:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(name, **kw)

    @contextlib.contextmanager
    def window(self):
        """The measured window: set-up ends where it starts.  With
        tracing, the profiler records exactly this window."""
        import jax

        if self.tracing:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(str(self.trace_dir), profiler_options=opts)
        t0 = time.perf_counter()
        self.t_window = (t0, math.nan)
        self.e2e["setup_s"] = t0 - T_PROCESS
        try:
            with self.span("bench.window"):
                yield
        finally:
            t1 = time.perf_counter()
            self.t_window = (t0, t1)
            if self.tracing:
                jax.profiler.stop_trace()

    def elapsed(self) -> float:
        """Seconds since the window opened."""
        return time.perf_counter() - self.t_window[0]

    def check(self, name: str, value: float, limit: float) -> None:
        """One compared number: passes when ``value <= limit``."""
        self.checks.append((name, float(value), float(limit),
                            bool(value <= limit)))


def device_info(jax, devices) -> dict:
    peak = 0
    for d in devices:
        try:
            peak = max(peak, int(d.memory_stats()["peak_bytes_in_use"]))
        except (KeyError, TypeError, RuntimeError):
            pass
    d0 = jax.devices()[0]
    return {
        "platform": d0.platform,
        "kind": d0.device_kind,
        "count": len(jax.devices()),
        "memory_peak_bytes": peak,
    }


def require_chips(jax, chips: int) -> list:
    """The cell's devices; exits 1 without a TPU or with too few chips."""
    devices = jax.devices()
    d0 = devices[0]
    print(f"device platform={d0.platform} kind={d0.device_kind} "
          f"count={len(devices)} jax={jax.__version__}", flush=True)
    if d0.platform != "tpu":
        print("FAIL: no TPU found; this benchmark has no CPU path",
              file=sys.stderr)
        sys.exit(1)
    if len(devices) < chips:
        print(f"FAIL: the cell needs {chips} chips, {len(devices)} found",
              file=sys.stderr)
        sys.exit(1)
    return devices[:chips]


def enable_cache(jax) -> str:
    """The program's compile cache (a fixed path in the checkout, or
    $JAX_COMPILATION_CACHE_DIR), keeping every program however quick to
    compile, so that only a cell's first run in a checkout compiles."""
    from repro.runtime.compile_cache import enable_compile_cache

    path = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def execute(run: Run, *, need_chip: bool = True) -> dict:
    """Set up, measure and check one run; returns the result object.

    ``need_chip=False`` skips the look for a TPU (the benchmark's own
    tests drive a run on the CPU with the timed path broken)."""
    sys.path.insert(0, str(ROOT / "src"))
    import jax

    jax.config.update("jax_enable_x64", False)
    if need_chip:
        run.devices = require_chips(jax, run.chips)
    else:
        run.devices = jax.devices()[: run.chips]
    print(f"compile cache {enable_cache(jax)}", flush=True)
    jax.monitoring.register_event_duration_secs_listener(run.on_compile)

    run.device_kind = jax.devices()[0].device_kind
    driver = load_module(run.bench.driver_path(run.traffic), "driver")
    state = driver.measure(run)
    device = device_info(jax, run.devices)
    if run.tracing:
        tr = load_module(HERE / "trace.py", "trace")
        run.trace = tr.reduce_run(run.trace_dir)
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
    driver.verify(run, state)
    del state

    correct = (
        run.attempted > 0
        and run.failed == 0
        and bool(run.checks)
        and all(ok for *_, ok in run.checks)
    )
    metrics = {}
    if run.tracing:
        for m in run.bench.cell_metrics("per_layer", run.cell["name"]):
            reader = load_module(run.bench.metric_path(m["name"]),
                                 "metric_" + m["name"].replace(".", "_"))
            value = reader.read(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in run.bench.cell_metrics("end_to_end", run.cell["name"]):
            metrics[m["name"]] = {"value": run.e2e[m["name"]],
                                  "unit": m["unit"]}
    out = {
        "correct": correct,
        "attempted": run.attempted,
        "failed": run.failed,
        "metrics": metrics,
        "device": device,
    }
    if run.tracing and run.trace is not None:
        out["breakdown"] = {
            "device_ops": run.trace["device_ops"],
            "idle_gaps": run.trace["idle_gaps"],
        }
    out["checks"] = {
        name: {"value": value, "limit": limit}
        for name, value, limit, _ in run.checks
    }
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--list", action="store_true",
                    help="resolve every entry to its files and exit")
    args = ap.parse_args(argv)

    try:
        bench = Benchmark()
        if args.list:
            for what, path in bench.resolve():
                print(f"{what:40s} {path.relative_to(ROOT)}")
            return 0
        if args.workload not in bench.workloads:
            print(f"FAIL: no workload {args.workload!r}", file=sys.stderr)
            return 1
        seconds = (bench.spec["run_seconds"] if args.seconds is None
                   else args.seconds)
        run = Run(bench, args.workload, args.seed, seconds, bool(args.trace))
        out = execute(run)
    except Exception:  # the boundary: report, print no result
        traceback.print_exc()
        return 1
    for name, value, limit, ok in run.checks:
        print(f"check {name} {value!r} limit {limit!r} "
              f"{'ok' if ok else 'FAILED'}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
