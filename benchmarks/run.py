"""Benchmark harness: one registered spec per paper table/figure.

Every bench module exposes ``run() -> List[row]`` (rows are
``{"name", "us_per_call", "derived"}`` dicts) plus optional module-level
``CONFIG`` / ``SEED`` constants and an optional summary payload
(returned as the second element of a ``(rows, payload)`` tuple).  The
registry drives them all and writes one uniform, machine-diffable
``BENCH_<name>.json`` per bench::

    {"name": ..., "config": {...}, "seed": ...,
     "metrics": {row-name: {"us_per_call": ..., "derived": ...}},
     "summary": {...}}        # module payload, when it has one

so the perf trajectory across PRs is a JSON diff, not a CSV scrape.
The legacy ``name,us_per_call,derived`` CSV still lands on stdout.

``python -m benchmarks.run [--smoke] [--only NAME ...] [--outdir DIR]
[--list]`` — JSONs land in ``bench_out/`` by default (kept out of the
repo root); ``--list`` prints the registry and exits.
"""
from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple


@dataclass(frozen=True)
class BenchSpec:
    """One benchmark: module + how to invoke it."""

    name: str  # BENCH_<name>.json and --only key
    title: str  # paper anchor, printed to stderr
    module: str  # import path under benchmarks/
    smoke_aware: bool = False  # run(smoke=...) supported


REGISTRY: Tuple[BenchSpec, ...] = (
    BenchSpec("alpha_calibration", "S3, Tables 1-2", "benchmarks.bench_alpha_calibration"),
    BenchSpec("simulations", "S7, Figures 13-14", "benchmarks.bench_simulations"),
    BenchSpec("online", "S7 dynamic: PM vs static vs proportional", "benchmarks.bench_online", smoke_aware=True),
    BenchSpec("two_node", "S6.1, Theorem 8", "benchmarks.bench_two_node"),
    BenchSpec("fptas", "S6.2, Corollary 19", "benchmarks.bench_fptas"),
    BenchSpec("discretization", "DESIGN S7 adaptation", "benchmarks.bench_discretization"),
    BenchSpec("kernel", "frontal Pallas", "benchmarks.bench_kernel"),
    BenchSpec("executor", "PM vs PROPORTIONAL, measured", "benchmarks.bench_executor"),
    BenchSpec("async", "futures vs wave barrier, straggler-injected A/B", "benchmarks.bench_async", smoke_aware=True),
    BenchSpec("workloads", "zoo trees: PM vs proportional vs online + expert placement", "benchmarks.bench_workloads", smoke_aware=True),
    BenchSpec("memory", "memory-bounded: pm vs pm-bounded budget sweep (arXiv:1210.2580)", "benchmarks.bench_memory", smoke_aware=True),
    BenchSpec("amalgamate", "tree amalgamation: threshold Pareto, many-small-fronts", "benchmarks.bench_amalgamate", smoke_aware=True),
    BenchSpec("obs", "telemetry: fluid-ratio fidelity, zero-overhead disable, span hygiene", "benchmarks.bench_obs", smoke_aware=True),
    BenchSpec("serve", "serving cluster: QPS/latency under Poisson load, cross-tenant batching A/B", "benchmarks.bench_serve", smoke_aware=True),
)


def write_bench_json(
    name: str,
    rows: List[Dict],
    *,
    config: Optional[Dict] = None,
    seed: Optional[int] = None,
    summary: Optional[Dict] = None,
    outdir: str = ".",
) -> str:
    """Write the uniform BENCH_<name>.json; returns the path."""
    doc: Dict = {
        "name": name,
        "config": config or {},
        "seed": seed,
        "metrics": {
            r["name"]: {
                "us_per_call": r["us_per_call"],
                "derived": r["derived"],
            }
            for r in rows
        },
    }
    if summary is not None:
        doc["summary"] = summary
    os.makedirs(outdir, exist_ok=True)
    path = os.path.join(outdir, f"BENCH_{name}.json")
    with open(path, "w") as f:
        json.dump(doc, f, indent=1)
    return path


def run_spec(
    spec: BenchSpec, *, smoke: bool = False, outdir: str = "."
) -> List[Dict]:
    """Run one bench, write its JSON, return its rows."""
    mod = importlib.import_module(spec.module)
    kwargs = {"smoke": smoke} if spec.smoke_aware else {}
    result = mod.run(**kwargs)
    if isinstance(result, tuple):
        rows, summary = result
    else:
        rows, summary = result, None
    write_bench_json(
        spec.name,
        rows,
        config=getattr(mod, "CONFIG", {}),
        seed=getattr(mod, "SEED", None),
        summary=summary,
        outdir=outdir,
    )
    return rows


def main(argv: Optional[List[str]] = None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true", help="small sizes for CI")
    ap.add_argument(
        "--only", nargs="*", help="run only these bench names", default=None
    )
    ap.add_argument(
        "--outdir", default="bench_out", help="where BENCH_*.json land"
    )
    ap.add_argument(
        "--list", action="store_true", help="print the registry and exit"
    )
    args = ap.parse_args(argv)

    if args.list:
        for spec in REGISTRY:
            print(f"{spec.name:20s} {spec.title}  [{spec.module}]")
        return

    names = {s.name for s in REGISTRY}
    if args.only:
        unknown = set(args.only) - names
        if unknown:
            ap.error(f"unknown bench(es) {sorted(unknown)}; known: {sorted(names)}")

    from repro.runtime.compile_cache import enable_compile_cache

    enable_compile_cache()
    print("name,us_per_call,derived")
    for spec in REGISTRY:
        if args.only and spec.name not in args.only:
            continue
        print(f"# --- {spec.name} ({spec.title})", file=sys.stderr)
        for r in run_spec(spec, smoke=args.smoke, outdir=args.outdir):
            print(f"{r['name']},{r['us_per_call']},{r['derived']}")
            sys.stdout.flush()


if __name__ == "__main__":
    main()
