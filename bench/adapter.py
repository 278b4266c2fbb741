"""The benchmark's one coupling to the program: build the served model from a
configuration file (through its architecture module, ``bench/arch``), and
drive ``PagedServingEngine.run`` as an open loop.

``PagedServingEngine`` has no wall-clock submit API: ``Request.arrival`` is
in ticks and ``run()`` blocks until every request is done.  So every request
is handed to ``run()`` at once, and four methods of the engine *instance*
are wrapped (the class and ``src/`` are untouched; no part of ``run`` is
copied):

* ``_admit`` passes on only requests whose due time has passed, and sleeps
  until the next due time when the engine has nothing else to do.  Once the
  starting population decodes, the pre-roll's arrivals begin; the window
  opens ``preroll_s`` later.  At the window's end the loop serves on only
  until every request due in the window has had its first token (at most
  ``traffic.FOLLOW_S``), then ends ``run()`` by raising, without waiting
  for the rest of what is in flight;
* ``_drain`` stamps each token as delivered when the drain hands it to the
  host;
* ``_prefill_step`` and ``_tick_block`` record what each call works on.

Each wrapper opens a host span (``jax.profiler.TraceAnnotation``) so that a
trace can say what the host was doing in each idle gap of the device.
"""
from __future__ import annotations

import collections
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import arch as archs
from bench import weights as W
from bench.traffic import Traffic


def make_engine(cj: dict, seed: int, arch=None):
    """Model, weights (one jitted call on the device) and engine.  ``arch``
    is the configuration's architecture module (``bench/arch``), found by
    the name the file gives where it is not passed."""
    from repro.launch.serve import PagedServingEngine
    from repro.models import LanguageModel

    arch = arch or archs.of(cj)
    model = LanguageModel(arch.program_config(cj))
    m = cj["model"]
    served = jnp.dtype(cj["dtype"]["weights"])

    def build(key):
        return arch.program_tree(model, m, key, served)

    key = W.seed_key(seed)
    want = model.abstract_params()
    got = jax.eval_shape(build, key)
    if jax.tree.structure(got) != jax.tree.structure(want) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype)
            for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want))):
        raise ValueError("bench weights do not match the program's "
                         "parameter tree")
    params = jax.jit(build)(key)
    e = cj["engine"]
    eng = PagedServingEngine(model, params, n_slots=e["n_slots"],
                             max_len=e["max_len"], page_size=e["page_size"],
                             dtype=jnp.dtype(cj["dtype"]["kv_cache"]))
    return eng


def warm_up(eng) -> None:
    """Compile every program the window drives: one prompt per rung of the
    chunk ladder, each with output enough for a decode block."""
    from repro.launch.paged_kv import chunk_ladder
    from repro.launch.serve import Request

    eng.run([Request(rid=-1 - i, prompt=[1] * c, max_new=eng.drain_every + 1)
             for i, c in enumerate(chunk_ladder(eng.chunk_max))])


# ---------------------------------------------------------------------------
# open loop
# ---------------------------------------------------------------------------


class WindowClosed(Exception):
    """Raised inside ``run()`` when the loop ends."""


@dataclasses.dataclass
class Stamp:
    due: float | None  # host clock; None for the starting population
    admit: float | None = None
    first: float | None = None  # first delivery, any time
    deliveries: list[tuple[float, int]] = dataclasses.field(
        default_factory=list)  # (time, tokens) inside the window


class OpenLoop:
    """Runs ``eng`` on ``traffic`` for ``seconds`` of window.

    ``on_open`` / ``on_close`` are called as the window opens and closes
    (a traced run starts and stops the profiler there).  ``clock`` and
    ``sleep`` may be replaced together (tests run on a simulated clock)."""

    def __init__(self, eng, traffic: Traffic, seconds: float,
                 on_open=None, on_close=None, clock=time.perf_counter,
                 sleep=time.sleep):
        from repro.launch.serve import Request

        self.eng = eng
        self.seconds = seconds
        self.preroll_s = traffic.preroll_s
        self.follow_s = traffic.follow_s
        self.clock, self.sleep = clock, sleep
        self.on_open, self.on_close = on_open, on_close
        self.t_open: float | None = None  # planned once the pre-roll starts
        self.t_end: float | None = None
        self.t_closed: float | None = None  # the window span ended
        self.t_stop: float | None = None  # the loop ended
        self.opened = False
        self.offsets = {p.rid: p.due_s for p in traffic.requests}
        self.pre_offsets = {p.rid: p.due_s for p in traffic.preroll}
        self.population = {p.rid for p in traffic.population}
        self.reqs = [Request(rid=p.rid, prompt=p.prompt, max_new=p.max_new)
                     for p in (traffic.population + traffic.preroll
                               + traffic.requests)]
        self.stamps = {r.rid: Stamp(due=None) for r in self.reqs}
        self._window_reqs = [r for r in self.reqs if r.rid in self.offsets]
        self.rounds: list[list[tuple[int, int, bool]]] = []  # in window
        self.blocks: list[list[tuple[int, int]]] = []  # (pos, ticks) per slot
        self._orig = {name: getattr(eng, name) for name in
                      ("_admit", "_drain", "_prefill_step", "_tick_block")}
        eng._admit = self._admit
        eng._drain = self._drain
        eng._prefill_step = self._prefill_step
        eng._tick_block = self._tick_block

    # ------------------------------------------------------------- window
    @property
    def in_window(self) -> bool:
        return self.opened and self.t_closed is None

    def _start_preroll(self) -> None:
        self.t_open = self.clock() + self.preroll_s
        self.t_end = self.t_open + self.seconds
        for offsets in (self.pre_offsets, self.offsets):
            for rid, off in offsets.items():
                self.stamps[rid].due = self.t_open + off

    def _check(self) -> None:
        """Open the window when its time has come; close it at its end;
        end the loop once the requests due in it have had first tokens."""
        if self.t_open is None:
            return
        now = self.clock()
        if not self.opened and now >= self.t_open:
            self.opened = True
            if self.on_open:
                self.on_open()
        if now < self.t_end:
            return
        if self.t_closed is None:
            self.t_closed = now
            if self.on_close:
                self.on_close()
        if now >= self.t_end + self.follow_s or all(
                self.stamps[r.rid].first is not None or r.rejected
                for r in self._window_reqs):
            raise WindowClosed

    def _population_decoding(self) -> bool:
        pf = {st.req.rid for st in self.eng._pf.values()}
        return all(self.stamps[rid].admit is not None and rid not in pf
                   for rid in self.population)

    def _idle(self) -> bool:
        e = self.eng
        return not (e._active or e._pf or e._finished)

    # ----------------------------------------------------------- wrappers
    def _admit(self, queue: collections.deque, now: int) -> None:
        if self.t_open is None and self._population_decoding():
            self._start_preroll()
        self._check()
        with jax.profiler.TraceAnnotation("admit"):
            due, later = self._split(queue)
            if not due and later and self._idle() and self.t_open is not None:
                nxt = min(self.stamps[r.rid].due for r in later)
                if not self.opened:
                    nxt = min(nxt, self.t_open)
                with jax.profiler.TraceAnnotation("idle_wait"):
                    self.sleep(max(0.0, min(nxt, self.t_end) - self.clock()))
                self._check()
                due, later = self._split(queue)
            q = collections.deque(due)
            self._orig["_admit"](q, now)
            t = self.clock()
            left = set(map(id, q))
            for r in due:
                if id(r) not in left and not r.rejected:
                    self.stamps[r.rid].admit = t
            queue.clear()
            queue.extend(list(q) + later)

    def _split(self, queue):
        t = self.clock()
        due, later = [], []
        for r in queue:
            d = self.stamps[r.rid].due
            pop = r.rid in self.population
            (due if pop or (d is not None and d <= t) else later).append(r)
        return due, later

    def _prefill_step(self) -> None:
        self._check()
        before = {slot: (st.start, len(st.req.prompt))
                  for slot, st in self.eng._pf.items()}
        with jax.profiler.TraceAnnotation("prefill_round"):
            self._orig["_prefill_step"]()
        members = []
        for slot, (start, plen) in before.items():
            st = self.eng._pf.get(slot)
            new = st.start if st is not None else plen
            if new > start:
                members.append((start, new - start, st is None))
        if self.in_window and members:
            self.rounds.append(members)

    def _tick_block(self, *args):
        self._check()
        e = self.eng
        if self.in_window:
            block = []
            for slot in e._active:
                req = e.slot_req[slot]
                left = int(e._remaining_h[slot])
                block.append((len(req.prompt) + req.max_new - left,
                              min(left, e.drain_every)))
            self.blocks.append(block)
        with jax.profiler.TraceAnnotation("decode_block"):
            return self._orig["_tick_block"](*args)

    def _drain(self, now: int) -> None:
        self._check()
        e = self.eng
        held = [e.slot_req[s] for s in e._active | e._finished]
        before = {r.rid: len(r.out) for r in held}
        with jax.profiler.TraceAnnotation("drain"):
            self._orig["_drain"](now)
        t = self.clock()
        for r in held:
            n = len(r.out) - before[r.rid]
            if n <= 0:
                continue
            st = self.stamps[r.rid]
            if st.first is None:
                st.first = t
            if self.opened and self.t_open <= t <= self.t_end:
                st.deliveries.append((t, n))

    # ---------------------------------------------------------------- run
    def run(self) -> None:
        try:
            self.eng.run(list(self.reqs))
            if self.t_open is None:  # everything ended during set-up
                self._start_preroll()
            with jax.profiler.TraceAnnotation("idle_wait"):
                self.sleep(max(0.0, self.t_end - self.clock()))
            self._check()
        except WindowClosed:
            pass
        finally:
            self.t_stop = self.clock()
            if self.t_closed is None and self.opened:
                self.t_closed = self.t_stop
                if self.on_close:
                    self.on_close()
            for name, fn in self._orig.items():
                setattr(self.eng, name, fn)
            self._orig = {}  # bound methods would keep the engine alive

    # ------------------------------------------------------------ results
    def finished(self) -> list:
        """Requests that delivered their last token by the window's end."""
        return [r for r in self.reqs if r.done and not r.rejected
                and len(r.out) == r.max_new]

    def unanswered(self) -> int:
        """Requests due in the window, not refused, that had no first token
        when the loop ended: answers that never came."""
        return sum(self.stamps[r.rid].first is None and not r.rejected
                   for r in self._window_reqs)

    @property
    def failed(self) -> int:
        return sum(r.rejected for r in self.reqs)

    def _waits(self, field: str) -> np.ndarray:
        """Due -> ``field`` of every request due in the window, followed
        past its end; one still waiting when the loop ends counts at its
        wait so far."""
        out = []
        for rid in self.offsets:
            st = self.stamps[rid]
            got = getattr(st, field)
            out.append((self.t_stop if got is None else got) - st.due)
        return np.asarray(out)

    def ttft_s(self) -> np.ndarray:
        return self._waits("first")

    def queue_wait_s(self) -> np.ndarray:
        return self._waits("admit")

    def tpot_s(self) -> np.ndarray:
        """(last - first delivery) / tokens delivered after the first, over
        requests with two or more deliveries in the window."""
        out = []
        for st in self.stamps.values():
            d = st.deliveries
            if len(d) >= 2:
                out.append((d[-1][0] - d[0][0]) / sum(n for _, n in d[1:]))
        return np.asarray(out)

    def tokens_in_window(self) -> int:
        return sum(n for st in self.stamps.values() for _, n in st.deliveries)
