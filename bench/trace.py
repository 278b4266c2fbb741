"""From a profiler trace (``.xplane.pb``) to device busy time, per-program
device time, the busiest operations, and idle gaps labelled with what the
host was doing.

Device planes are those named ``/device:TPU:<n>``.  On each, the line
``XLA Modules`` holds one event per program execution (``jit_tick_block``,
``jit_chunk``, ...) and ``XLA Ops`` one per operation.  Busy time is the
union of the operations' intervals (of the modules' where a plane has no
op line).  The benchmark's own host spans (``jax.profiler.TraceAnnotation``)
are read from the host plane; ``bench_window`` marks the measured window,
and everything is clipped to it.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re

WINDOW_SPAN = "bench_window"
HOST_SPANS = ("admit", "prefill_round", "decode_block", "drain", "idle_wait")
_SUFFIX = re.compile(r"\(\d+\)$")
_LAYOUT = re.compile(r"\{[^{}]*\}")


@dataclasses.dataclass
class Reduced:
    window: tuple[float, float]  # ns
    n_devices: int
    busy_ns: float  # per device, averaged
    programs: dict[str, tuple[float, int]]  # name -> (ns, calls), per device
    ops: dict[str, float]  # op name -> ns, per device
    gaps: list[tuple[str, float]]  # (host span, ns), device 0, in order

    @property
    def window_ns(self) -> float:
        return self.window[1] - self.window[0]

    def program_ns(self, name: str) -> float:
        return self.programs.get(name, (0.0, 0))[0]


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(s: float, e: float, w: tuple[float, float]):
    s, e = max(s, w[0]), min(e, w[1])
    return (s, e) if e > s else None


def _label(gap: tuple[float, float], spans) -> str:
    """The host span that overlaps the gap most; of nested spans that
    overlap it alike, the innermost."""
    best, best_ns = "host", 0.0
    for name, s, e in sorted(spans, key=lambda sp: sp[2] - sp[1]):
        ov = min(e, gap[1]) - max(s, gap[0])
        if ov > best_ns:
            best, best_ns = name, ov
    return best


def _op_name(hlo: str) -> str:
    """An op event is named by its whole HLO instruction: keep the name and
    the result's type, without layouts, cut to 96 characters."""
    return _LAYOUT.sub("", hlo)[:96]


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {trace_dir}, "
                                f"found {paths}")
    return paths[0]


def reduce(data) -> Reduced:
    """``data`` is a ``jax.profiler.ProfileData``."""
    spans, windows = [], []
    devices = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU"):
            devices.append(plane)
            continue
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == WINDOW_SPAN:
                    windows.append((ev.start_ns, ev.end_ns))
                elif ev.name in HOST_SPANS:
                    spans.append((ev.name, ev.start_ns, ev.end_ns))
    if not devices:
        raise ValueError("the trace has no TPU device plane")

    per_dev = []
    for plane in devices:
        lines = {line.name: list(line.events) for line in plane.lines}
        per_dev.append((lines.get("XLA Modules", []),
                        lines.get("XLA Ops", [])))
    if windows:
        window = (min(s for s, _ in windows), max(e for _, e in windows))
    else:
        evs = [ev for mods, _ in per_dev for ev in mods]
        if not evs:
            raise ValueError("no program ran on the device in the trace")
        window = (min(ev.start_ns for ev in evs),
                  max(ev.end_ns for ev in evs))

    busy = 0.0
    programs: dict[str, list[float]] = collections.defaultdict(
        lambda: [0.0, 0])
    ops: dict[str, float] = collections.defaultdict(float)
    gaps: list[tuple[str, float]] = []
    for i, (mods, op_evs) in enumerate(per_dev):
        for ev in mods:
            c = _clip(ev.start_ns, ev.end_ns, window)
            if c:
                p = programs[_SUFFIX.sub("", ev.name)]
                p[0] += c[1] - c[0]
                p[1] += 1
        ivs = []
        for ev in op_evs or mods:
            c = _clip(ev.start_ns, ev.end_ns, window)
            if c:
                ivs.append(c)
                if op_evs:
                    ops[_op_name(ev.name)] += c[1] - c[0]
        merged = _union(ivs)
        busy += sum(e - s for s, e in merged)
        if i == 0:
            edges = [window[0]] + [t for iv in merged for t in iv] \
                + [window[1]]
            for s, e in zip(edges[::2], edges[1::2]):
                if e > s:
                    gaps.append((_label((s, e), spans), e - s))

    n = len(devices)
    return Reduced(
        window=window, n_devices=n, busy_ns=busy / n,
        programs={k: (v[0] / n, int(v[1] / n)) for k, v in programs.items()},
        ops={k: v / n for k, v in ops.items()},
        gaps=gaps)


def breakdown(r: Reduced, top: int = 10) -> dict:
    """The contract's ``breakdown``: busiest device operations and longest
    idle gaps, in seconds."""
    ops = sorted(r.ops.items(), key=lambda kv: -kv[1])[:top] or sorted(
        ((k, v[0]) for k, v in r.programs.items()), key=lambda kv: -kv[1]
    )[:top]
    gaps = sorted(r.gaps, key=lambda g: -g[1])[:top]
    return {"device_ops": [[k, v / 1e9] for k, v in ops],
            "idle_gaps": [[k, v / 1e9] for k, v in gaps]}
