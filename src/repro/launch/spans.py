"""How the serving loop describes itself to a profile.

``Spans`` keeps a bounded ring of the loop's host spans on
``time.perf_counter()``, and opens a ``jax.profiler.TraceAnnotation`` of the
same name for each, so a profiler trace holds the same spans on its host
plane, on the clock of the device's events.  Spans are always recorded: with
the profiler off one costs two clock reads and an append.

``hlo_ops`` reads the named scope of every operation out of the text of a
compiled program (``jax.named_scope`` lands in ``metadata={op_name=...}``),
so device time per operation in a trace can be summed per scope.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import gc
import re
import time

import jax

# one stable name per layer of the model step; the innermost names an op
SCOPES = ("embed", "attn", "mla", "recurrent", "kv_pool", "moe", "mlp",
          "lm_head", "sample")


class Span:
    """``name``, ``t0`` and ``t1`` on ``time.perf_counter()``, the name of
    the span it ran inside (``parent``), the requests it touched (``rids``)
    and its ``args``."""

    __slots__ = ("name", "t0", "t1", "parent", "rids", "args")

    def __init__(self, name: str, parent: str | None, rids: list[int],
                 args: dict, t0: float = 0.0, t1: float = 0.0):
        self.name, self.parent, self.rids, self.args = name, parent, rids, args
        self.t0, self.t1 = t0, t1

    def __repr__(self) -> str:
        return (f"Span({self.name!r}, {self.t1 - self.t0:.6f} s, "
                f"parent={self.parent!r}, rids={self.rids}, {self.args})")


class Spans:
    """The ring holds the last ``capacity`` spans to finish: at the serving
    loop's mix (a block's span names 32 requests) about 375 bytes each, so
    6 MB at the bound, and some 20 minutes of a loop that records a dozen
    spans a second.  A span is opened and closed in order: ``open`` and
    ``close``, or the block of ``with spans(name)``."""

    def __init__(self, capacity: int = 1 << 14):
        self.ring: collections.deque[Span] = collections.deque(
            maxlen=capacity)
        # (span, its annotation) of the spans open, innermost last
        self._open: list[tuple[Span, jax.profiler.TraceAnnotation]] = []

    def _parent(self) -> str | None:
        return self._open[-1][0].name if self._open else None

    def open(self, name: str, rids=(), **args) -> Span:
        """Open a span; until it is closed, its ``rids`` and ``args`` may
        be added to."""
        sp = Span(name, self._parent(), list(rids), args)
        ann = jax.profiler.TraceAnnotation(name)
        ann.__enter__()
        self._open.append((sp, ann))
        sp.t0 = time.perf_counter()
        return sp

    def close(self, sp: Span) -> None:
        """Close the innermost open span, ``sp``."""
        top, ann = self._open.pop()
        assert top is sp, (top, sp)
        if ann.is_enabled():
            ann.set_metadata(**sp.args, rids=" ".join(map(str, sp.rids)))
        sp.t1 = time.perf_counter()
        ann.__exit__(None, None, None)
        self.ring.append(sp)

    @contextlib.contextmanager
    def __call__(self, name: str, rids=(), **args):
        sp = self.open(name, rids, **args)
        try:
            yield sp
        finally:
            self.close(sp)

    @contextlib.contextmanager
    def running(self):
        """Around the serving loop: while open, each collection of Python's
        collector is a ``gc`` span with its generation; on leaving, the
        spans an exception left open are closed."""
        started: list = []

        def hook(phase: str, info: dict) -> None:
            if phase == "start":
                ann = jax.profiler.TraceAnnotation(
                    "gc", generation=info["generation"])
                ann.__enter__()
                started.append((ann, time.perf_counter()))
            elif started:  # not a collection begun before the hook
                ann, t0 = started.pop()
                t1 = time.perf_counter()
                ann.__exit__(None, None, None)
                self.ring.append(Span(
                    "gc", self._parent(), [],
                    {"generation": info["generation"],
                     "collected": info["collected"]}, t0, t1))

        gc.callbacks.append(hook)
        try:
            yield
        finally:
            gc.callbacks.remove(hook)
            while self._open:
                self.close(self._open[-1][0])


# ---------------------------------------------------------------------------
# named scopes in compiled programs
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class HloOp:
    """One instruction of a compiled program that runs as an operation of
    its own: ``name`` (``fusion.241``), ``line`` (its text, from the name
    on, without metadata), the innermost of ``SCOPES`` in its op_name (None
    for none), and ``leaf``: False for a loop or branch, whose operations
    run, and are timed, as operations of their own."""
    name: str
    line: str
    scope: str | None
    leaf: bool


_COMP = re.compile(r"^(ENTRY )?%?([\w.\-]+) .*\{\s*$")
_INSTR = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = (.*)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_OPCODE = re.compile(r"[\])}] ([\w\-]+)\(")  # after the result's type
_CALLED = re.compile(r"\b(?:body|condition|branch_computations|"
                     r"true_computation|false_computation|to_apply)="
                     r"\{?([^}\s]+)")
_CONTROL = ("while", "conditional", "call")
_FUSED = re.compile(r"\bcalls=%?([\w.\-]+)")


def _scope(op_name: str) -> str | None:
    for part in reversed(op_name.split("/")):
        if part in SCOPES:
            return part
    return None


def _fused_scope(instrs: list[tuple[str, str]]) -> str | None:
    """A fusion without an op_name of its own is named by the scope nearest
    its output: that of the last instruction inside it that has one."""
    for _, rest in reversed(instrs):
        m = _OP_NAME.search(rest)
        if m and _scope(m.group(1)):
            return _scope(m.group(1))
    return None


def hlo_ops(text: str) -> list[HloOp]:
    """The operations of a compiled program's text (``Compiled.as_text()``)
    that run on the device: those of the entry computation and of the
    computations its loops and branches run, not those inside fusions."""
    comps: dict[str, list[tuple[str, str]]] = {}
    entry, cur = None, None
    for raw in text.splitlines():
        m = _COMP.match(raw)
        if m and " = " not in raw.split("(")[0]:
            cur = comps.setdefault(m.group(2), [])
            if m.group(1):
                entry = m.group(2)
            continue
        m = _INSTR.match(raw)
        if m and cur is not None:
            cur.append((m.group(1), m.group(2)))
    out: list[HloOp] = []
    todo, seen = [entry], set()
    while todo:
        comp = todo.pop(0)
        if comp is None or comp in seen or comp not in comps:
            continue
        seen.add(comp)
        for name, rest in comps[comp]:
            body = rest.split(", metadata={")[0]
            m = _OPCODE.search(body)
            opcode = m.group(1) if m else ""
            if opcode in _CONTROL:  # the computations a loop or branch runs
                for called in _CALLED.findall(body):
                    todo.extend(c.strip("%") for c in called.split(","))
            m = _OP_NAME.search(rest)
            scope = _scope(m.group(1)) if m else None
            if scope is None and opcode == "fusion":
                m = _FUSED.search(body)
                scope = _fused_scope(comps.get(m.group(1), []) if m else [])
            out.append(HloOp(name, f"%{name} = {body}", scope,
                             opcode not in _CONTROL))
    return out
