#!/usr/bin/env python3
"""Record the small device trace that ``test_trace.py`` checks.

Runs on a TPU only.  It warms, then traces, a few front-factor calls of
the program under test: two batched whole-front-in-VMEM dispatches and one
large front through the panel + SYRK path, inside the same host spans the
benchmark writes (``bench.window`` around everything, ``bench.factorize``
around each unit of work), with a 20 ms host sleep between them so the
trace holds a known idle gap.  The trace lands in
``<out>/plugins/profile/<time>/*.xplane.pb``; ``--list`` prints every
event of every plane, so the expected numbers of the check can be worked
out by hand.

Usage:  python benchmarks/chip/record_trace.py --out chiprun_out/trace [--list]
"""
from __future__ import annotations

import argparse
import glob
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[2]


def spd_front(m: int, rng) -> np.ndarray:
    g = rng.standard_normal((m, m)).astype(np.float32) / np.sqrt(m)
    return (g @ g.T + np.eye(m, dtype=np.float32)).astype(np.float32)


def padded_stack(b: int, m: int, mp: int, rng) -> np.ndarray:
    out = np.broadcast_to(np.eye(mp, dtype=np.float32), (b, mp, mp)).copy()
    for k in range(b):
        out[k, :m, :m] = spd_front(m, rng)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", required=True)
    ap.add_argument("--list", action="store_true")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    if jax.devices()[0].platform != "tpu":
        print("no TPU found", file=sys.stderr)
        return 1
    sys.path.insert(0, str(ROOT / "src"))
    from repro.kernels.ops import batched_front_factor, partial_cholesky

    rng = np.random.default_rng(7)
    small = jnp.asarray(padded_stack(4, 100, 128, rng))
    mid = jnp.asarray(padded_stack(2, 200, 256, rng))
    large = jnp.asarray(spd_front(1200, rng))

    def work(i: int):
        with jax.profiler.TraceAnnotation("bench.factorize", i=i):
            if i == 0:
                batched_front_factor(small, 128).block_until_ready()
            else:
                batched_front_factor(mid, 128).block_until_ready()
                jax.block_until_ready(partial_cholesky(large, 600))

    work(0)
    work(1)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(args.out, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        work(0)
        time.sleep(0.02)
        work(1)
    jax.profiler.stop_trace()
    path = sorted(glob.glob(f"{args.out}/**/*.xplane.pb", recursive=True))[-1]
    print(f"trace {path} {Path(path).stat().st_size} bytes")
    if args.list:
        from jax.profiler import ProfileData

        for plane in ProfileData.from_file(path).planes:
            print(f"plane {plane.name!r}")
            for line in plane.lines:
                evs = list(line.events)
                print(f"  line {line.name!r} {len(evs)} events")
                for e in evs:
                    stats = {k: str(v)[:80] for k, v in e.stats}
                    print(
                        f"    {e.name[:100]!r} start={e.start_ns} "
                        f"dur={e.duration_ns} {json.dumps(stats)}"
                    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
