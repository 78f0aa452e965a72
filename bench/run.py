#!/usr/bin/env python3
"""Run one benchmark cell on the chips of this machine.

    python3 bench/run.py --workload cosmo_all --seed 7 --seconds 20 --trace 0

The cell is looked up in ``BENCHMARK.json`` at the root of the checkout.
With ``--trace 0`` the result carries the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics: host spans, counters and ``repro.obs``
spans of the window, which runs with the profiler off, and a profiler trace
of a few seconds of steps after it.  The last line of standard output
is one JSON object (``correct``, ``attempted``, ``failed``, ``metrics``,
``device``, and with ``--trace 1`` ``breakdown``), and the last lines of
standard error are the numbers compared, each beside its limit.

It refuses to run, and prints no result, when JAX finds no TPU, fewer chips
than the cell asks for, or no ``src/repro`` beside it.
"""

from __future__ import annotations

import time

T_PROC = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    try:
        from bench import harness
        import repro.core  # noqa: F401
    except ImportError as e:
        print(f"bench: the system under test is not in this checkout: {e}",
              file=sys.stderr)
        return 2
    bench = harness.load_benchmark()
    cell = harness.find_cell(bench, args.workload)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"bench: no TPU: JAX found {len(devices)} "
              f"{devices[0].platform!r} device(s); the benchmark runs only "
              f"on TPU chips", file=sys.stderr)
        return 1
    if len(devices) < int(cell["chips"]):
        print(f"bench: {args.workload} needs {cell['chips']} chips, JAX "
              f"found {len(devices)}", file=sys.stderr)
        return 1
    cache = harness.use_compile_cache()
    print(f"compile cache: {cache}", file=sys.stderr, flush=True)

    def log(s: str) -> None:
        print(s, file=sys.stderr, flush=True)

    result = harness.run_cell(args.workload, args.seed, args.seconds,
                              bool(args.trace), devices[:int(cell["chips"])],
                              T_PROC, log=log)
    harness.print_checks(result["checks"])
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
