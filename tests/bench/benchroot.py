"""A checkout-shaped directory for the benchmark's CPU tests: a copy of
``bench/`` beside a ``BENCHMARK.json`` whose cells use the smoke-size
configurations and mix under ``tests/bench/fixtures``."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
FIXTURES = os.path.join(REPO, "tests", "bench", "fixtures")
for _p in (REPO, os.path.join(REPO, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

CELLS = {"dense.smoke": "smoke-dense", "moe.smoke": "smoke-moe"}


def fixture(name: str) -> dict:
    with open(os.path.join(FIXTURES, name)) as f:
        return json.load(f)


def make_root(path: str, dtype: dict | None = None) -> str:
    """Copy ``bench/`` to ``path`` with the smoke cells.  ``dtype``, if
    given, replaces the configurations' dtypes."""
    root = str(path)
    shutil.copytree(os.path.join(REPO, "bench"), os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"], bench["workloads"] = [], []
    for metric in bench["end_to_end"] + bench["per_layer"]:
        metric.pop("workloads", None)  # every metric applies to the smoke cells
    for cell, cfg in CELLS.items():
        cj = fixture(f"{cfg}.json")
        if dtype:
            cj["dtype"] = dtype
        rel = f"bench/configs/{cfg}.json"
        with open(os.path.join(root, rel), "w") as f:
            json.dump(cj, f)
        bench["configs"].append({"name": cfg, "source": cj["source"],
                                 "file": rel, "reduced": cj["reduced"],
                                 "why": "smoke size, CPU tests only"})
        bench["workloads"].append({"name": cell, "config": cfg,
                                   "traffic": "smoke_mix", "chips": 1,
                                   "why": "smoke size, CPU tests only"})
    shutil.copy(os.path.join(FIXTURES, "smoke_mix.json"),
                os.path.join(root, "bench", "traffic", "smoke_mix.json"))
    write_bench(root, bench)
    return root


def read_bench(root: str) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def write_bench(root: str, bench: dict) -> None:
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f, indent=1)


def run_bench(root: str, *args, env_extra: dict | None = None):
    """``bench/run.py`` of ``root`` in a process of its own, on the CPU."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(REPO, "src"))
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, os.path.join(root, "bench", "run.py"), *args],
        capture_output=True, text=True, env=env, timeout=600)


class SimClock:
    """A clock that advances ``step`` seconds per reading, and by the
    duration of each sleep: runs on it do not depend on the host's speed."""

    def __init__(self, step: float = 0.01):
        self.t = 0.0
        self.step = step

    def __call__(self) -> float:
        self.t += self.step
        return self.t

    def sleep(self, seconds: float) -> None:
        self.t += seconds
