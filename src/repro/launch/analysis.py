"""Compiled-artifact analysis: cost_analysis, memory_analysis, and collective
byte accounting parsed from the post-SPMD HLO (shapes there are per-device
shard shapes, which is exactly the per-chip roofline denominator).
"""
from __future__ import annotations

import re
from typing import Any

import numpy as np

from repro.core.platforms import HBM_BW, ICI_BW, PEAK_FLOPS

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

# one shape: f32[16,128]{1,0}; tuples: (f32[1,2]{...}, bf16[3]{...})
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_OP_RE = re.compile(
    r"=\s+(\([^)]*\)|\w+\[[\d,]*\][^\s]*)\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(-start|-done)?\(", )
_COMP_HDR_RE = re.compile(r"^(?:ENTRY )?%([\w.\-]+) \(.*\) -> .+ \{")
_WHILE_RE = re.compile(r"condition=%([\w.\-]+), body=%([\w.\-]+)")
_CALL_RE = re.compile(r"(?:to_apply|calls)=%([\w.\-]+)")
_BRANCH_RE = re.compile(r"branch_computations=\{([^}]*)\}")
_CONST_RE = re.compile(r"constant\((\d+)\)")


def _shape_bytes(type_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _shape_bytes_f32(type_str: str) -> int:
    """Bytes contributed by f32 sub-shapes only (see dtype correction)."""
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt != "f32":
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * 4
    return total


def _split_computations(hlo_text: str) -> dict[str, str]:
    comps: dict[str, list[str]] = {}
    name = None
    for ln in hlo_text.splitlines():
        m = _COMP_HDR_RE.match(ln)
        if m and not ln.startswith(" "):
            name = m.group(1)
            comps[name] = []
        elif name is not None:
            if ln.startswith("}"):
                name = None
            else:
                comps[name].append(ln)
    return {k: "\n".join(v) for k, v in comps.items()}


def collective_bytes(hlo_text: str) -> dict[str, float]:
    """Per-device bytes moved by each collective kind, with while-loop bodies
    scaled by their trip counts (XLA cost analysis counts loop bodies once —
    scan-over-layers would otherwise be under-counted by ~n_layers x).

    Byte proxy per op = result-shape bytes ('-done' halves of async pairs are
    skipped).  Trip count = the loop bound constant in the condition region.
    """
    comps = _split_computations(hlo_text)

    kinds_all = _COLLECTIVES + ("f32_portion",)
    own: dict[str, dict[str, float]] = {}
    own_counts: dict[str, dict[str, int]] = {}
    refs: dict[str, list[tuple[str, float]]] = {}
    for name, text in comps.items():
        o = {k: 0.0 for k in kinds_all}
        c = {k: 0 for k in _COLLECTIVES}
        for m in _OP_RE.finditer(text):
            type_str, kind, phase = m.group(1), m.group(2), m.group(3)
            if phase == "-done":
                continue
            o[kind] += _shape_bytes(type_str)
            o["f32_portion"] += _shape_bytes_f32(type_str)
            c[kind] += 1
        own[name] = o
        own_counts[name] = c
        r: list[tuple[str, float]] = []
        for ln in text.splitlines():
            wm = _WHILE_RE.search(ln)
            if wm:
                cond, body = wm.groups()
                consts = [int(x) for x in _CONST_RE.findall(comps.get(cond, ""))]
                trip = float(max([x for x in consts if x > 0] or [1]))
                r.append((body, trip))
                continue
            bm = _BRANCH_RE.search(ln)
            if bm:
                for b in bm.group(1).split(","):
                    b = b.strip().lstrip("%")
                    if b:
                        r.append((b, 1.0))
            for cm in _CALL_RE.finditer(ln):
                r.append((cm.group(1), 1.0))
        refs[name] = r

    memo: dict[str, dict[str, float]] = {}

    def total(name: str, stack: frozenset) -> dict[str, float]:
        if name in memo:
            return memo[name]
        if name not in own or name in stack:
            return {k: 0.0 for k in kinds_all}
        acc = dict(own[name])
        for child, mult in refs[name]:
            sub = total(child, stack | {name})
            for k in kinds_all:
                acc[k] += mult * sub[k]
        memo[name] = acc
        return acc

    entry = None
    for ln in hlo_text.splitlines():
        if ln.startswith("ENTRY "):
            m = _COMP_HDR_RE.match(ln)
            if m:
                entry = m.group(1)
    if entry is None:
        entry = max(comps, key=lambda k: len(comps[k])) if comps else ""

    out: dict[str, Any] = dict(total(entry, frozenset())) if entry else \
        {k: 0.0 for k in kinds_all}
    out["total_raw"] = sum(out[k] for k in _COLLECTIVES)
    # dtype correction: the CPU backend normalizes bf16 -> f32 *before* SPMD
    # partitioning (verified on a minimal sharded bf16 matmul), so every f32
    # collective here would move bf16 on TPU.  Genuinely-f32 tensors in this
    # codebase (loss stats, router logits) are tiny, so halving the f32
    # portion is the honest TPU estimate; both values are reported.
    out["total"] = out["total_raw"] - 0.5 * out.pop("f32_portion")
    static = {k: sum(own_counts[n][k] for n in own_counts)
              for k in _COLLECTIVES}
    out["op_counts"] = static
    return out


def top_collectives(hlo_text: str, k: int = 12) -> list[dict[str, Any]]:
    """The §Perf diagnostic: largest collectives by trip-scaled bytes,
    with their shapes and loop multipliers."""
    comps = _split_computations(hlo_text)

    # compute the execution multiplier of every computation (entry = 1)
    mult: dict[str, float] = {}
    entry = None
    for ln in hlo_text.splitlines():
        if ln.startswith("ENTRY "):
            m = _COMP_HDR_RE.match(ln)
            if m:
                entry = m.group(1)
    if entry is None and comps:
        entry = max(comps, key=lambda kk: len(comps[kk]))

    def walk(name: str, m: float, stack: frozenset) -> None:
        if name not in comps or name in stack:
            return
        mult[name] = mult.get(name, 0.0) + m
        for ln in comps[name].splitlines():
            wm = _WHILE_RE.search(ln)
            if wm:
                cond, body = wm.groups()
                consts = [int(x) for x in _CONST_RE.findall(comps.get(cond, ""))]
                trip = float(max([x for x in consts if x > 0] or [1]))
                walk(body, m * trip, stack | {name})
                continue
            bm = _BRANCH_RE.search(ln)
            if bm:
                for b in bm.group(1).split(","):
                    b = b.strip().lstrip("%")
                    if b:
                        walk(b, m, stack | {name})
            for cm in _CALL_RE.finditer(ln):
                walk(cm.group(1), m, stack | {name})

    if entry:
        walk(entry, 1.0, frozenset())

    rows = []
    for name, text in comps.items():
        m = mult.get(name, 0.0)
        if m <= 0:
            continue
        for om in _OP_RE.finditer(text):
            type_str, kind, phase = om.group(1), om.group(2), om.group(3)
            if phase == "-done":
                continue
            b = _shape_bytes(type_str)
            rows.append({
                "kind": kind, "shape": type_str[:90], "bytes": b,
                "trips": m, "total_bytes": b * m, "computation": name[:60],
            })
    rows.sort(key=lambda r: -r["total_bytes"])
    return rows[:k]


def safe_cost_analysis(compiled: Any) -> dict[str, float]:
    try:
        ca = compiled.cost_analysis()
        return {k: float(v) for k, v in ca.items()
                if isinstance(v, (int, float, np.floating))}
    except Exception as e:  # pragma: no cover
        return {"error": -1.0, "_msg": str(e)}  # type: ignore[dict-item]


def safe_memory_analysis(compiled: Any) -> dict[str, float]:
    try:
        ma = compiled.memory_analysis()
        if ma is None:
            return {}
        out = {}
        for k in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "generated_code_size_in_bytes",
                  "alias_size_in_bytes", "host_argument_size_in_bytes",
                  "peak_memory_in_bytes"):
            if hasattr(ma, k):
                out[k] = float(getattr(ma, k))
        return out
    except Exception:  # pragma: no cover
        return {}


def argument_bytes(lowered_args: Any) -> float:
    """Fallback per-device residency: sum of sharded argument sizes."""
    import jax

    total = 0.0
    for leaf in jax.tree.leaves(lowered_args):
        if not hasattr(leaf, "shape"):
            continue
        n = float(np.prod(leaf.shape)) if leaf.shape else 1.0
        n *= np.dtype(leaf.dtype).itemsize
        sh = getattr(leaf, "sharding", None)
        if sh is not None and hasattr(sh, "num_devices") and sh.num_devices:
            try:
                shard_shape = sh.shard_shape(leaf.shape)
                n = float(np.prod(shard_shape)) * np.dtype(leaf.dtype).itemsize
            except Exception:
                n /= sh.num_devices
        total += n
    return total


def roofline(flops_per_device: float, hbm_bytes_per_device: float,
             coll_bytes_per_device: float, model_flops_total: float,
             n_chips: int) -> dict[str, float]:
    t_comp = flops_per_device / PEAK_FLOPS
    t_mem = hbm_bytes_per_device / HBM_BW
    t_coll = coll_bytes_per_device / ICI_BW
    terms = {"compute": t_comp, "memory": t_mem, "collective": t_coll}
    bottleneck = max(terms, key=terms.get)  # type: ignore[arg-type]
    step_time = max(terms.values())
    useful = model_flops_total / max(1.0, flops_per_device * n_chips)
    mfu = (model_flops_total / n_chips / PEAK_FLOPS) / max(step_time, 1e-12)
    return {
        "t_compute_s": t_comp,
        "t_memory_s": t_mem,
        "t_collective_s": t_coll,
        "bottleneck": bottleneck,  # type: ignore[dict-item]
        "step_time_s": step_time,
        "useful_flops_ratio": useful,
        "model_flops_util": mfu,
    }
