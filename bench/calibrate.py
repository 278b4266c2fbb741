"""Readings that the limits of ``correct`` are set from, for one cell.

    python3 bench/calibrate.py --workload granite-moe-1b.decode \
        --seeds 11,12,13 --seconds 51 [--fault cache_unchanged]

In one process, for each seed, one run of the cell as ``bench/run.py``
makes it (``run.run_cell``), whose sample is compared twice: as the program
served it, and with the float8 control in the program's place.  One JSON
line per seed: both verdicts with the numbers compared, the run's
end-to-end metrics and its device.  The program's readings over a dozen
seeds give each limit's lower end, the control's its upper end.
``--fault`` plants a fault of ``bench/faults.py`` in the timed path.  The
benchmark's own runs never run the control.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (ROOT, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

# libtpu would write its logs under /tmp: a fixed path outside the checkout
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402

from bench import faults, run  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated whole numbers")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--fault", choices=sorted(faults.FAULTS))
    args = ap.parse_args()
    if jax.devices()[0].platform != "tpu":
        print("calibrate: JAX found no TPU; nothing was run", file=sys.stderr)
        return run.NO_DEVICE
    run.set_compile_cache(ROOT)
    hook = faults.FAULTS[args.fault] if args.fault else None
    t = T_START
    for seed in (int(s) for s in args.seeds.split(",")):
        out = run.run_cell(ROOT, args.workload, seed, args.seconds,
                           trace=False, t_start=t, setup_hook=hook,
                           control=True)
        print(json.dumps({
            "workload": args.workload, "seed": seed, "fault": args.fault,
            "seconds": args.seconds, "correct": out["correct"],
            "checks": out["checks"], "control": out["control"],
            "metrics": {k: v["value"] for k, v in out["metrics"].items()},
            "attempted": out["attempted"], "device": out["device"]}),
            flush=True)
        t = time.perf_counter()
    return 0


if __name__ == "__main__":
    sys.exit(main())
