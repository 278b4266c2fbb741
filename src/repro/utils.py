"""Small shared helpers (no jax device-state side effects at import)."""
from __future__ import annotations

import math
import os
import pathlib
import time
from typing import Any, Iterator

import jax
import numpy as np


#: fixed, git-ignored home of the persistent compile cache inside the
#: checkout: a path that never changes lets a later run find what an
#: earlier one compiled
REPO_COMPILE_CACHE = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache for an entry point.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting and is left
    alone; otherwise the cache goes to ``REPO_COMPILE_CACHE``.  Returns the
    directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_COMPILE_CACHE))
    return str(REPO_COMPILE_CACHE)


def tree_size(tree: Any) -> int:
    """Total number of elements across all leaves."""
    return sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree) if hasattr(x, "shape"))


def tree_bytes(tree: Any) -> int:
    total = 0
    for x in jax.tree.leaves(tree):
        if hasattr(x, "shape") and hasattr(x, "dtype"):
            total += int(np.prod(x.shape)) * np.dtype(x.dtype).itemsize
    return total


def human_bytes(n: float) -> str:
    for unit in ("B", "KiB", "MiB", "GiB", "TiB", "PiB"):
        if abs(n) < 1024.0:
            return f"{n:.2f} {unit}"
        n /= 1024.0
    return f"{n:.2f} EiB"


def human_count(n: float) -> str:
    for unit in ("", "K", "M", "B", "T"):
        if abs(n) < 1000.0:
            return f"{n:.2f}{unit}"
        n /= 1000.0
    return f"{n:.2f}Q"


def human_time(seconds: float) -> str:
    if seconds < 1e-3:
        return f"{seconds * 1e6:.1f} us"
    if seconds < 1.0:
        return f"{seconds * 1e3:.2f} ms"
    if seconds < 120.0:
        return f"{seconds:.2f} s"
    return f"{seconds / 60.0:.1f} min"


class Stopwatch:
    def __enter__(self) -> "Stopwatch":
        self.start = time.perf_counter()
        return self

    def __exit__(self, *exc: Any) -> None:
        self.elapsed = time.perf_counter() - self.start


def round_up(x: int, multiple: int) -> int:
    return int(math.ceil(x / multiple) * multiple)


def chunks(seq: list, n: int) -> Iterator[list]:
    for i in range(0, len(seq), n):
        yield seq[i : i + n]
