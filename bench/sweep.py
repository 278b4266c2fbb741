"""Find a cell's knee: its traffic offered at several fixed rates, one window
each, in one process.

    python3 bench/sweep.py --workload granite-moe-1b.decode \
        --rates 0.2,0.3,0.4,0.5 --seconds 51 --seed 5

For each rate (the starting population and the pre-roll follow the rate, as
``bench/traffic.py`` derives them), one JSON line: requests offered in the
window, the backlog (due and no first token yet) at the window's opening
and at its end, the slots decoding at both, and the window's end-to-end
readings (with the mean time per output token, from which a mix's
``decode_s`` is derived).  The knee is the highest rate at which the
backlog at the end is no longer than at the opening.  The benchmark's cells
offer a fixed rate found this way; they never search.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (ROOT, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

# libtpu would write its logs under /tmp: a fixed path outside the checkout
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402

from bench import adapter, run, traffic  # noqa: E402


def window_at(cj: dict, arch, mix: dict, rate: float, seed: int,
              seconds: float) -> dict:
    mix = {**mix, "arrivals": {**mix["arrivals"], "rate_per_s": rate}}
    eng = adapter.make_engine(cj, seed, arch)
    tr = traffic.make_traffic(mix, seconds, seed, eng.model.cfg.vocab_size)
    adapter.warm_up(eng)
    active = {}

    def count_active():
        active[len(active)] = len(eng._active)

    loop = adapter.OpenLoop(eng, tr, seconds, on_open=count_active,
                            on_close=count_active)
    loop.run()

    def backlog(t, offsets):
        return sum(loop.stamps[rid].due <= t and (
            loop.stamps[rid].first is None or loop.stamps[rid].first > t)
            for rid in offsets)

    due = {**loop.pre_offsets, **loop.offsets}
    tpot = loop.tpot_s()
    out = {
        "rate_per_s": rate, "offered": len(loop.offsets),
        "population": len(tr.population), "preroll": len(tr.preroll),
        "backlog_open": backlog(loop.t_open, due),
        "backlog_end": backlog(loop.t_end, due),
        "decoding_open": active.get(0), "decoding_end": active.get(1),
        "ttft_p95_ms": float(np.percentile(loop.ttft_s(), 95) * 1e3),
        "tpot_p95_ms": (float(np.percentile(tpot, 95) * 1e3)
                        if tpot.size else None),
        "tpot_mean_ms": float(tpot.mean() * 1e3) if tpot.size else None,
        "output_tokens_per_s": loop.tokens_in_window() / seconds,
        "unanswered": loop.unanswered(),
        "follow_s": loop.t_stop - loop.t_end,
    }
    loop.eng = None
    del eng
    gc.collect()
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True, help="requests/s, comma-separated")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    if jax.devices()[0].platform != "tpu":
        print("sweep: JAX found no TPU; nothing was run", file=sys.stderr)
        return run.NO_DEVICE
    run.set_compile_cache(ROOT)
    _, _, cj, mix, arch = run.load_cell(ROOT, args.workload)
    for rate in (float(r) for r in args.rates.split(",")):
        print(json.dumps({"workload": args.workload, **window_at(
            cj, arch, mix, rate, args.seed, args.seconds)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
