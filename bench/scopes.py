"""Device self time per named scope, per program, in the traced window, and
the engine's own spans in the window.

The trace reduction (``bench/trace.py``) keeps device time per operation,
keyed by the operation's HLO text (``Reduced.ops``).  The engine names the
scope of every operation of the programs it dispatched
(``PagedServingEngine.hlo_ops``: ``jax.named_scope`` read out of each
compiled program's op metadata).  Joined on the instruction's name, result
type and operands, they give ``scope_ns``: ``{program: {scope: ns}}``, over leaf
operations only, so a loop (``%while``), whose operations are timed on
their own, does not count twice.  An operation with no scope goes under
``other``.

An operation the reduction holds that no program names is left out and
counted in ``unmatched_ns``.  One that two programs name alike (the copy of
a page pool both take as an argument) is split between them in proportion
to the device time of each program (``Reduced.programs``) that its own
operations leave unaccounted for; one that a program's executables scope
differently, evenly between the scopes.
"""
from __future__ import annotations

import collections
import re

from bench import trace

OTHER = "other"
_INDEX = re.compile(r"/\*index=\d+\*/")
_OPCODE = re.compile(r"[\])}] ([\w\-]+)\(")
_OPERAND = re.compile(r"%[\w.\-]+")


def _parts(text: str) -> tuple[str, list[str]]:
    """An instruction's head (name, result type and opcode, without
    layouts: ``%fusion.241 = bf16[24,4096,16,8,64] fusion(``) and the names
    of what follows it (operands and called computations), in order."""
    text = _INDEX.sub("", trace._LAYOUT.sub("", text)).split("/*")[0]
    m = _OPCODE.search(text)
    if m is None:
        return text, []
    return text[:m.end()], _OPERAND.findall(text[m.end():])


def _name(key: str) -> str:
    return key.split(" = ", 1)[0].lstrip("%")


def _agree(key: tuple[str, list[str]], op: tuple[str, list[str]]) -> bool:
    """A trace's op key (cut to a length, so its last word may be cut too)
    agrees with an instruction as far as the key goes."""
    (head_k, names_k), (head, names) = key, op
    n = min(len(head_k), len(head))
    if head_k[:n] != head[:n]:
        return False
    if len(names_k) > len(names):
        return False
    *whole, last = names_k or [""]
    return (names[:len(whole)] == whole
            and (not names_k or names[len(whole)].startswith(last)))


class ScopeTimes:
    """``ns``: ``{program: {scope: ns}}`` of leaf operations;
    ``unmatched_ns``: device time of operations no program names."""

    def __init__(self, ns: dict, unmatched_ns: float):
        self.ns = ns
        self.unmatched_ns = unmatched_ns

    def get(self, program: str, scope: str) -> float:
        return self.ns.get(program, {}).get(scope, 0.0)

    def program_ns(self, program: str) -> float:
        return sum(self.ns.get(program, {}).values())


def scope_ns(reduced, hlo_ops: dict) -> ScopeTimes:
    """``reduced``: a ``trace.Reduced``; ``hlo_ops``: ``{program: [[op,
    ...] per executable]}`` with each op's ``name``, ``line``, ``scope``
    and ``leaf``."""
    by_name: dict[str, list[tuple]] = collections.defaultdict(list)
    for program, executables in hlo_ops.items():
        for ops in executables:
            for op in ops:
                by_name[op.name].append((program, _parts(op.line),
                                         op.scope or OTHER, op.leaf))
    ns: dict[str, dict[str, float]] = collections.defaultdict(
        lambda: collections.defaultdict(float))
    unmatched = 0.0
    shared = []  # (ns, {program: [scopes]}) of names two programs hold
    for key, t in reduced.ops.items():
        parts = _parts(key)
        cands = [c for c in by_name.get(_name(key), ())
                 if _agree(parts, c[1])]
        if not cands:
            unmatched += t
            continue
        if not all(leaf for *_, leaf in cands):
            continue  # a loop: its operations are timed on their own
        progs: dict[str, set] = collections.defaultdict(set)
        for program, _, scope, _ in cands:
            progs[program].add(scope)
        if len(progs) == 1:
            (program, scopes_), = progs.items()
            for scope in scopes_:
                ns[program][scope] += t / len(scopes_)
        else:
            shared.append((t, progs))
    # a name two programs hold alike goes where each program's device time
    # is not yet accounted for by its own operations
    left = {p: max(0.0, reduced.program_ns(p)
                   - sum(ns.get(p, {}).values()))
            for _, progs in shared for p in progs}
    for t, progs in shared:
        total = sum(left[p] for p in progs)
        for program, scopes_ in progs.items():
            share = left[program] / total if total else 1 / len(progs)
            for scope in scopes_:
                ns[program][scope] += t * share / len(scopes_)
    return ScopeTimes({p: dict(s) for p, s in ns.items()}, unmatched)


def for_ctx(ctx) -> ScopeTimes | None:
    """The scope times of a metric's context, or None where there is no
    trace or the program names no scopes."""
    eng = getattr(ctx.loop, "eng", None)
    if ctx.reduced is None or not hasattr(eng, "hlo_ops"):
        return None
    # a profile names a program's executions jit_<function>
    return scope_ns(ctx.reduced, {"jit_" + name: exes
                                  for name, exes in eng.hlo_ops().items()})


def window(ctx) -> tuple[float, float]:
    """The window on the host clock: opened, and closed (or due to)."""
    loop = ctx.loop
    return loop.t_open, loop.t_closed if loop.t_closed is not None \
        else loop.t_end


def window_spans(ctx, name: str) -> list | None:
    """The engine's spans called ``name`` that started inside the window,
    or None where the program records no spans."""
    spans = getattr(getattr(ctx.loop, "eng", None), "spans", None)
    if spans is None or ctx.loop.t_open is None:
        return None
    t0, t1 = window(ctx)
    return [s for s in spans.ring if s.name == name and t0 <= s.t0 < t1]


def per_unit(ctx, program: str, scope: str, span: str, arg: str,
             unit: float) -> float | None:
    """Milliseconds of ``scope`` in ``program`` per ``unit`` of the ``arg``
    summed over the window's ``span`` spans."""
    spans = window_spans(ctx, span)
    st = for_ctx(ctx) if spans else None
    if st is None or not st.program_ns(program):
        return None
    n = sum(s.args.get(arg, 0) for s in spans)
    return st.get(program, scope) / 1e6 / (n / unit) if n else None
