#!/usr/bin/env python3
"""Read ``correct`` for sound runs and for planted faults of one cell.

    python3 bench/control.py --workload cosmo_all --seconds 5 \\
        --seeds 101 102 103 --faults none control stale half altered dropped

Every run is a whole run of the cell, at its own size and load, with a
short window, all in this one process; ``none`` is the sound program.  One
line per run gives the fault, the seed, ``correct`` and each number
compared.  It exits 0 only when every sound run is correct and every
planted fault is caught.  The benchmark's own runs (``bench/run.py``)
never plant a fault; this script runs on a TPU only, like them.
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
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--faults", nargs="+", default=["none", "control"])
    args = ap.parse_args(argv)

    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import faults, harness

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"control: no TPU ({devices[0].platform!r})", file=sys.stderr)
        return 1
    cell = harness.find_cell(harness.load_benchmark(), args.workload)
    harness.use_compile_cache()
    ok = True
    for fault in args.faults:
        for seed in args.seeds:
            with faults.plant(fault):
                res = harness.run_cell(args.workload, seed, args.seconds,
                                       False, devices[:int(cell["chips"])],
                                       time.monotonic())
            caught = not res["correct"]
            ok &= caught == (fault != "none")
            print(json.dumps({"fault": fault, "seed": seed,
                              "correct": res["correct"],
                              "attempted": res["attempted"],
                              "checks": {k: v["value"] for k, v in
                                         res["checks"].items()}}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
