"""Architectures, found by name.

A configuration file names its architecture (``"architecture": "llama"``),
and the harness loads ``bench/arch/<name>.py`` from the checkout, as it
finds a mix or a metric's reader.  Adding an architecture is adding that
file.  The module holds everything that is specific to one architecture:

* ``program_config(cj)``: the program's ``ModelConfig`` for the file, which
  it refuses where the program would compute another block;
* ``layer_shapes(m)`` and ``global_shapes(m)``: (shape, std) of each tensor
  that ``bench/weights.py`` makes from the seed (std 0 means ones);
* ``program_tree(model, m, key, served)``: the program's parameter tree,
  filled from those tensors;
* ``Reference(m, seed, served_dtype)``, whose ``logits(tokens, rows,
  fp8=False)`` is the plain float32 forward pass (``fp8=True``: the
  float8 control), with the same tensors;
* ``prefill_round(m, members)`` and ``decode_tick(m, positions)``: the
  operations and bytes (``bench.counts.Work``) that one call needs.

``m`` is the file's ``model`` dict, in the architecture's own keys.
"""
from __future__ import annotations

import importlib.util
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def load(name: str, root: str | None = None):
    """``bench/arch/<name>.py`` under ``root`` (by default, the checkout
    that holds this package)."""
    base = os.path.join(root, "bench", "arch") if root else _HERE
    path = os.path.join(base, f"{name}.py")
    if not os.path.isfile(path):
        raise ValueError(f"architecture {name!r}: there is no file {path}")
    spec = importlib.util.spec_from_file_location(
        "bench_arch_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def of(cj: dict, root: str | None = None):
    """The architecture module that configuration ``cj`` names."""
    if "architecture" not in cj:
        raise ValueError(f"{cj['name']}: the file names no 'architecture'")
    return load(cj["architecture"], root)


def scaled_config(cj: dict, fields: dict[str, str]):
    """The program's registered config ``cj["program_config"]`` with the
    file's sizes: ``fields`` maps a key of ``cj["model"]`` to a
    ``ModelConfig`` field.  A size that differs from the registered one must
    be in ``reduced``."""
    from repro.configs import get_config

    base = get_config(cj["program_config"])
    m = cj["model"]
    over = {}
    for key, field in fields.items():
        if getattr(base, field) != m[key]:
            if key not in cj["reduced"]:
                raise ValueError(f"{cj['name']}: {key} = {m[key]} in the file "
                                 f"but {getattr(base, field)} in the program, "
                                 f"and {key} is not in 'reduced'")
            over[field] = m[key]
    return base.scaled(**over, param_dtype=cj["dtype"]["weights"],
                       compute_dtype=cj["dtype"]["compute"])
