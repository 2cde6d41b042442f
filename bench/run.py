#!/usr/bin/env python3
"""Run one benchmark cell once, on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout.  Builds the cell's engine with weights made
from the seed, warms every shape its traffic uses (set-up, ``setup_s``),
serves the traffic for ``--seconds`` through ``ServingEngine``'s control
plane and event loop, then compares what the window produced with the
plain float32 reference (``bench/gfbench/reference.py`` and the
configuration's architecture, ``bench/archs/<architecture>.py``).  With
``--trace 1`` a profiler trace of the window's steady part gives the
per-layer metrics and a breakdown.

The last line of standard output is one JSON object with ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown``
when traced), then ``checks``: each number compared with its limit,
which the last lines of standard error repeat.  A backend other than
TPU, a ``device_kind`` missing from ``bench/peaks.json``, too few chips,
or a checkout without the program exits non-zero with no result.

``--control 1`` also prints, under ``control``, the same numbers for
the bfloat16 reference put in the program's place (the control that
``correct`` must reject); the benchmark's own runs never ask for it.
"""
import time

T_START = time.monotonic()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
CACHE_DIR = ".jax_cache"
# run records and reduced traces (the profiler's own files are deleted)
OUT_DIR = "bench_out"
sys.path.insert(0, str(ROOT / "bench"))

from gfbench import bench, spec  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    table = spec.load()
    cell = spec.resolve(table, args.workload)
    if not (ROOT / "src" / "repro").is_dir():
        print("bench/run.py: no program (src/repro) in this checkout",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))

    import jax
    from jax import monitoring
    # a fixed directory inside the checkout, whatever the environment
    # says, so that two checkouts never share compiled programs
    jax.config.update("jax_compilation_cache_dir", str(ROOT / CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    print(f"compile cache: {jax.config.jax_compilation_cache_dir}",
          file=sys.stderr)
    compiles: list = []
    monitoring.register_event_duration_secs_listener(
        lambda ev, dur, **kw: compiles.append((time.monotonic(), dur))
        if ev == "/jax/core/compile/backend_compile_duration" else None)
    try:
        peak = bench.device_check(jax.devices(), spec.peaks(), cell["chips"])
    except bench.Refused as e:
        print(f"bench/run.py: {e}", file=sys.stderr)
        return 1
    out = ROOT / OUT_DIR
    out.mkdir(parents=True, exist_ok=True)
    result = bench.measure(
        cell, args.seed, bench.run_seconds(table, args.seconds),
        bool(args.trace), out, peak, T_START, compiles,
        control=bool(args.control))
    bench.report(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
