#!/usr/bin/env python3
"""Record the small device trace that ``test_program_spans.py`` checks.

Runs on a TPU only.  It analyzes and plans a 31 x 31 five-point Laplacian
(961 unknowns, the program's nested dissection), factors it once to
compile every shape, then traces, inside the same host spans the
benchmark writes (``bench.window`` around everything, ``bench.factorize``
around each ``Session.execute``), two factorizations with a 20 ms host
sleep between them, outside any span of the program.  The program's
stage spans (``session.execute``, ``executor.*``) land on the host plane.
The trace lands in ``<out>/plugins/profile/<time>/*.xplane.pb``; ``--list``
prints every program and benchmark span of the host plane, line by line.

Usage:  python benchmarks/chip/record_program_spans.py --out benchmarks/chip/out/spans [--list]
"""
from __future__ import annotations

import argparse
import glob
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
GRID = 31
SLEEP_S = 0.02


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", required=True)
    ap.add_argument("--list", action="store_true")
    args = ap.parse_args(argv)

    import jax

    if jax.devices()[0].platform != "tpu":
        print("no TPU found", file=sys.stderr)
        return 1
    sys.path.insert(0, str(ROOT / "src"))
    from repro.api import DeviceMesh, Session
    from repro.sparse import grid_laplacian_2d, nested_dissection_2d

    a = grid_laplacian_2d(GRID)
    sess = Session(DeviceMesh(jax.devices()[:1])).analyze(
        a, alpha=0.9, ordering=nested_dissection_2d(GRID)
    ).plan(policy="greedy")
    rep = sess.execute()
    print(f"fronts {sess.problem.symb.n_supernodes} "
          f"dispatches {rep.detail.n_dispatches}")

    def factorize(k: int) -> None:
        with jax.profiler.TraceAnnotation("bench.factorize", k=k):
            sess.execute(warmup=False)

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(args.out, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        factorize(1)
        time.sleep(SLEEP_S)
        factorize(2)
    jax.profiler.stop_trace()
    path = sorted(glob.glob(f"{args.out}/**/*.xplane.pb", recursive=True))[-1]
    print(f"trace {path} {Path(path).stat().st_size} bytes")
    if args.list:
        from jax.profiler import ProfileData

        for plane in ProfileData.from_file(path).planes:
            if not plane.name.startswith("/host:"):
                continue
            for i, line in enumerate(plane.lines):
                evs = [e for e in line.events
                       if e.name.startswith(("bench.", "session.", "executor."))]
                print(f"line {i} {line.name!r} {len(evs)} spans")
                for e in evs:
                    print(f"  {e.name} start={e.start_ns} dur={e.duration_ns}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
