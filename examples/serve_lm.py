"""Batched serving example: continuous batching over decode slots.

    PYTHONPATH=src python examples/serve_lm.py --arch granite-moe-1b-a400m --requests 6
"""
import argparse
import importlib

import jax
import numpy as np

from repro.launch.serve import ContinuousBatcher, Request
from repro.models import LanguageModel


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-moe-1b-a400m")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--max-new", type=int, default=12)
    ap.add_argument("--slots", type=int, default=3)
    args = ap.parse_args()

    mod = importlib.import_module(
        "repro.configs." + args.arch.replace("-", "_").replace(".", "_"))
    cfg = mod.smoke()
    model = LanguageModel(cfg)
    params = model.init(jax.random.PRNGKey(0))

    batcher = ContinuousBatcher(model, params, n_slots=args.slots,
                                max_len=64)
    rng = np.random.RandomState(0)
    reqs = [Request(rid=i,
                    prompt=rng.randint(0, cfg.vocab_size, 6).tolist(),
                    max_new=args.max_new)
            for i in range(args.requests)]
    stats = batcher.run(reqs)
    print(f"[serve {args.arch}] {stats['requests']} requests, "
          f"{stats['tokens']} tokens in {stats['wall_s']:.2f}s "
          f"({stats['tok_per_s']:.1f} tok/s, {stats['ticks']} ticks, "
          f"{args.slots} slots)")
    for r in reqs[:3]:
        print(f"  req {r.rid}: prompt {r.prompt} -> out {r.out}")


if __name__ == "__main__":
    main()
