"""Serving drivers: paged high-throughput engine + dense reference batcher.

Two implementations share the ``Request`` interface:

``PagedServingEngine`` (the production path)
    Block-table-backed paged KV cache (``launch/paged_kv.py``), chunked
    prefill interleaved with decode ticks (a long prompt never stalls the
    active streams), exact power-of-two prompt bucketing (bounded jit trace
    count, zero padding), device-resident decode state with on-device argmax,
    and a bounded host-sync cadence — outputs drain every ``drain_every``
    ticks instead of every tick.  Completion is deterministic (count-based),
    so the host schedules without reading the device between drains.

``ContinuousBatcher`` (the dense reference)
    The original lockstep batcher: dense ``(n_slots, max_len)`` caches, full
    unchunked prefill at admission (jit retraces per prompt length), one
    host sync per tick.  Kept as the benchmark baseline and the simplest
    correctness oracle.

Both report ``host_syncs`` and device↔host byte counters in their run stats
so regressions in host chatter show up in BENCH_serving.json, not just wall
time.
"""
from __future__ import annotations

import argparse
import collections
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.launch.paged_kv import PagedKVCache, decompose
from repro.launch.spans import HloOp, Spans, hlo_ops
from repro.launch.train import smoke_config
from repro.models import LanguageModel
from repro.utils import enable_compile_cache


@dataclasses.dataclass
class Request:
    rid: int
    prompt: list[int]
    max_new: int
    arrival: int = 0  # earliest admit tick (0 = already queued)
    out: list[int] = dataclasses.field(default_factory=list)
    done: bool = False
    rejected: bool = False
    admit_tick: int = -1
    finish_tick: int = -1
    # the paged engine's stamps, on time.perf_counter(): admitted to a
    # slot, and prefill finished (its finalize dispatched)
    t_admit: float | None = None
    t_prefilled: float | None = None


# ---------------------------------------------------------------------------
# Paged serving engine
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class _Prefilling:
    req: Request
    start: int  # next prompt position to compute
    frames: jax.Array | None = None


class PagedServingEngine:
    """Hundreds of concurrent streams over a shared paged KV pool.

    Per engine iteration: one device-resident *block* of ``drain_every``
    batched decode ticks (a ``lax.scan`` in a single dispatch; inactive
    slots are masked by ``pos == -1`` and mutate nothing), then up to
    ``prefill_chunks_per_tick`` prefill chunks for admitted-but-not-yet-
    decoding requests.  Output tokens accumulate in a device ring and drain
    to the host once per block; freed slots are recycled at drain
    boundaries.

    The engine records its own host spans (``self.spans``: ``admit``,
    ``prefill_round`` with a ``finalize`` per finished request,
    ``decode_block`` from dispatch until its tokens are ready, ``drain``
    with ``fetch`` and ``deliver``, and ``gc`` for each collection during
    ``run``), stamps each ``Request`` on the same clock, and keeps the
    abstract arguments of every program signature it dispatched, so that
    ``hlo_ops`` can name the scope of each device operation.
    """

    def __init__(self, model: LanguageModel, params, n_slots: int = 64,
                 max_len: int = 256, page_size: int = 16,
                 pool_fraction: float = 1.0, chunk_max: int = 64,
                 drain_every: int = 8, prefill_chunks_per_tick: int = 1,
                 prefill_group: int = 8, enc_len: int = 0,
                 dtype=jnp.bfloat16):
        self.model = model
        self.params = params
        self.n_slots = n_slots
        self.chunk_max = chunk_max
        self.drain_every = drain_every
        self.prefill_chunks_per_tick = prefill_chunks_per_tick
        self.prefill_group = prefill_group
        max_pages = -(-max_len // page_size)
        n_pages = max(1, int(n_slots * max_pages * pool_fraction))
        self.kv = PagedKVCache(model, n_slots, n_pages, page_size, max_pages,
                               enc_len=enc_len, dtype=dtype)

        B = n_slots
        self.last_token = jnp.zeros((B,), jnp.int32)
        self.pos = jnp.full((B,), -1, jnp.int32)
        self.remaining = jnp.zeros((B,), jnp.int32)
        self.out_buf = jnp.zeros((B, drain_every), jnp.int32)
        self.out_cnt = jnp.zeros((B,), jnp.int32)

        # host mirrors (decode emission is deterministic: one token per
        # active slot per tick, so no device reads are needed to schedule)
        self.slot_req: list[Request | None] = [None] * B
        self._active: set[int] = set()        # emitting slots
        self._finished: set[int] = set()      # done, tokens pending drain
        self._pf: collections.OrderedDict[int, _Prefilling] = \
            collections.OrderedDict()
        self._remaining_h = np.zeros((B,), np.int64)

        self.stats_counters = {
            "host_syncs": 0, "bytes_to_host": 0, "bytes_to_device": 0,
            "drains": 0, "prefill_chunks": 0, "decode_ticks": 0,
            "stall_ticks": 0,
        }
        self.spans = Spans()
        # (program, shapes that vary) -> abstract arguments of its first
        # dispatch, for hlo_ops
        self._signatures: dict[tuple, tuple] = {}

        def tick_block(params, cache, table, last, pos, remaining, out_buf,
                       out_cnt):
            """``drain_every`` decode ticks in one dispatch: the decode loop
            is device-resident between drains, so per-call overhead (pytree
            flattening, dispatch) is paid once per K tokens per slot."""
            def body(carry, _):
                cache, last, pos, remaining, out_buf, out_cnt = carry
                emit = remaining > 0
                pos_eff = jnp.where(emit, pos, -1)
                logits, cache = model.decode_step(params, last[:, None],
                                                  cache, pos_eff, table=table)
                with jax.named_scope("sample"):
                    nxt = jnp.argmax(logits, -1).astype(jnp.int32)
                    b = jnp.arange(B)
                    # emit the *input* token (seed semantics: the first
                    # emitted token is the post-prefill argmax); inactive
                    # columns land OOB -> dropped
                    col = jnp.where(emit, out_cnt, drain_every)
                    out_buf = out_buf.at[b, col].set(last)
                inc = emit.astype(jnp.int32)
                return (cache, jnp.where(emit, nxt, last), pos + inc,
                        remaining - inc, out_buf, out_cnt + inc), None

            carry, _ = jax.lax.scan(
                body, (cache, last, pos, remaining, out_buf, out_cnt),
                None, length=drain_every)
            return carry

        def chunk(params, cache, table, slots, tokens, start, frames):
            """One batched prefill round: G slots advance one chunk each.
            Padded group entries (slot == n_slots, start == -1) gather init
            values, compute garbage, and scatter out of bounds -> dropped."""
            with jax.named_scope("kv_pool"):
                rows = jnp.take(table, slots, axis=0, mode="fill",
                                fill_value=self.kv.n_pages)
                view = self.kv._gather_impl(cache, rows, slots)
            batch = {"tokens": tokens}
            if frames is not None:
                batch["frames"] = frames
            logits, view = model.prefill_chunk(params, batch, view, start)
            with jax.named_scope("kv_pool"):
                cache = self.kv._scatter_impl(cache, view, rows, slots)
            return cache, logits

        @jax.named_scope("sample")
        def finalize(last, pos, remaining, logits, slot, plen, max_new):
            tok = jnp.argmax(logits[0]).astype(jnp.int32)
            return (last.at[slot].set(tok), pos.at[slot].set(plen),
                    remaining.at[slot].set(max_new))

        # params are arguments, not closure constants: a closed-over array
        # is embedded in each compiled program, so every program would carry
        # its own copy of the weights.  The threaded state is donated so XLA
        # updates the cache pools in place instead of copying them every
        # block/chunk.
        self._tick_block = jax.jit(tick_block,
                                   donate_argnums=(1, 3, 4, 5, 6, 7))
        self._chunk = jax.jit(chunk, donate_argnums=(1,))
        self._finalize = jax.jit(finalize, donate_argnums=(0, 1, 2))
        # by name, for hlo_ops: the attributes above may be wrapped
        self._jitted = {f.__name__: f for f in (
            self._tick_block, self._chunk, self._finalize)}

    def _note(self, program: str, key, args: tuple) -> None:
        """Keep the abstract arguments of a program's first dispatch with
        this ``key`` (its shapes that vary), for ``hlo_ops``."""
        if (program, key) not in self._signatures:
            self._signatures[(program, key)] = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), args)

    def hlo_ops(self) -> dict[str, list[list[HloOp]]]:
        """The operations of every executable dispatched so far, per
        program (``tick_block``, ``chunk``, ``finalize``): one list per
        signature, each op with its named scope.  The executables come from
        JAX's in-memory cache of what already ran, so nothing is compiled."""
        out: dict[str, list[list[HloOp]]] = {}
        for (program, _), args in self._signatures.items():
            text = self._jitted[program].lower(*args).compile().as_text()
            out.setdefault(program, []).append(hlo_ops(text))
        return out

    # ----------------------------------------------------------- scheduling
    def _admit(self, queue: collections.deque[Request], now: int) -> None:
        """Scan the whole queue (no head-of-line blocking): any request whose
        page reservation fits an open slot is admitted; over-sized requests
        are rejected outright instead of wedging the queue."""
        free_slots = [s for s in range(self.n_slots)
                      if self.slot_req[s] is None]
        if not free_slots:
            return
        sp = self.spans.open("admit")
        t = time.perf_counter()
        keep: list[Request] = []
        while queue:
            req = queue.popleft()
            need = len(req.prompt) + req.max_new + 1
            if self.kv.pages_needed(need) > self.kv.max_pages:
                req.rejected = True
                req.done = True
                continue
            if free_slots and self.kv.can_alloc(need):
                slot = free_slots.pop(0)
                self.kv.alloc(slot, need)
                self.slot_req[slot] = req
                req.admit_tick = now
                req.t_admit = t
                sp.rids.append(req.rid)
                self._pf[slot] = _Prefilling(req=req, start=0)
            else:
                keep.append(req)
        queue.extend(keep)
        self.spans.close(sp)

    def _prefill_step(self) -> None:
        """One batched prefill round: the oldest prefilling request picks the
        chunk size, every other pending request at the same size joins the
        group (up to ``prefill_group``), one jit call advances them all."""
        if not self._pf:
            return
        _, oldest = next(iter(self._pf.items()))
        c = decompose(len(oldest.req.prompt) - oldest.start, self.chunk_max)[0]
        members = [
            (slot, st) for slot, st in self._pf.items()
            if decompose(len(st.req.prompt) - st.start, self.chunk_max)[0] == c
        ][:self.prefill_group]

        G = self.prefill_group
        sp = self.spans.open("prefill_round",
                             [st.req.rid for _, st in members], chunk=c,
                             tokens=c * len(members))  # c fits each member
        tokens = np.zeros((G, c), np.int32)
        starts = np.full((G,), -1, np.int32)
        slots = np.full((G,), self.n_slots, np.int32)  # pad -> OOB drop
        for i, (slot, st) in enumerate(members):
            tokens[i] = st.req.prompt[st.start:st.start + c]
            starts[i] = st.start
            slots[i] = slot
        tokens = jnp.asarray(tokens)
        self.stats_counters["bytes_to_device"] += int(tokens.nbytes)
        frames = members[0][1].frames
        args = (self.params, self.kv.cache, self.kv.table,
                jnp.asarray(slots), tokens, jnp.asarray(starts), frames)
        self._note("chunk", (c, frames is None), args)
        self.kv.cache, logits = self._chunk(*args)
        self.stats_counters["prefill_chunks"] += len(members)
        for i, (slot, st) in enumerate(members):
            st.start += c
            if st.start >= len(st.req.prompt):
                with self.spans("finalize", [st.req.rid]):
                    del self._pf[slot]
                    args = (self.last_token, self.pos, self.remaining,
                            logits[i][None], jnp.asarray(slot, jnp.int32),
                            jnp.asarray(len(st.req.prompt), jnp.int32),
                            jnp.asarray(st.req.max_new, jnp.int32))
                    self._note("finalize", (), args)
                    self.last_token, self.pos, self.remaining = \
                        self._finalize(*args)
                    st.req.t_prefilled = time.perf_counter()
                self._active.add(slot)
                self._remaining_h[slot] = st.req.max_new
        self.spans.close(sp)

    def _drain(self, now: int) -> None:
        held = list(self._active | self._finished)
        sp = self.spans.open("drain", [self.slot_req[s].rid for s in held])
        with self.spans("fetch"):
            out_buf, out_cnt = jax.device_get((self.out_buf, self.out_cnt))
        deliver = self.spans.open("deliver")
        self.stats_counters["host_syncs"] += 1
        self.stats_counters["bytes_to_host"] += (
            int(self.out_buf.nbytes) + int(self.out_cnt.nbytes))
        self.stats_counters["drains"] += 1
        for slot in held:
            req = self.slot_req[slot]
            req.out.extend(int(t) for t in out_buf[slot, :out_cnt[slot]])
            if slot in self._finished or len(req.out) >= req.max_new:
                req.done = True
                if req.finish_tick < 0:
                    req.finish_tick = now
                self.slot_req[slot] = None
                self.kv.free(slot)
                self._active.discard(slot)
                self._finished.discard(slot)
        self.out_cnt = jnp.zeros_like(self.out_cnt)
        self.spans.close(deliver)
        self.spans.close(sp)

    # ------------------------------------------------------------------ run
    def run(self, requests: list[Request]) -> dict:
        with self.spans.running():
            return self._run(requests)

    def _run(self, requests: list[Request]) -> dict:
        # re-entrant: a warm engine can serve successive traces (benchmarks
        # reuse one instance so jit compiles are paid once, not per run)
        self.stats_counters = dict.fromkeys(self.stats_counters, 0)
        pending = collections.deque(sorted(requests, key=lambda r: r.arrival))
        queue: collections.deque[Request] = collections.deque()
        t0 = time.time()
        ticks = 0
        ran_block = False
        block_s: list[float] = []  # each decode_block span's length
        K = self.drain_every
        while (pending or queue or self._active or self._finished
               or self._pf):
            while pending and pending[0].arrival <= ticks:
                queue.append(pending.popleft())
            self._admit(queue, ticks)

            if self._active:
                # one device-resident block: K decode ticks, zero host
                # reads; its span runs until the block's tokens are ready
                block = self.spans.open(
                    "decode_block", [self.slot_req[s].rid
                                     for s in self._active],
                    active=len(self._active), ticks=K)
                args = (self.params, self.kv.cache, self.kv.table,
                        self.last_token, self.pos, self.remaining,
                        self.out_buf, self.out_cnt)
                self._note("tick_block", (), args)
                (self.kv.cache, self.last_token, self.pos, self.remaining,
                 self.out_buf, self.out_cnt) = self._tick_block(*args)
                self.stats_counters["decode_ticks"] += K
                ran_block = True
                for slot in list(self._active):
                    left = self._remaining_h[slot]
                    if left <= K:
                        self._active.discard(slot)
                        self._finished.add(slot)
                        self.slot_req[slot].finish_tick = ticks + int(left)
                        self._remaining_h[slot] = 0
                    else:
                        self._remaining_h[slot] = left - K
                ticks += K
            elif self._pf:
                self.stats_counters["stall_ticks"] += 1
            elif pending and not queue:
                ticks = max(ticks, pending[0].arrival)  # idle until arrival

            # prefill backpressure: flood chunks while decode is
            # under-saturated (filling slots beats tail latency), trickle one
            # round per block once half the slots are streaming
            rounds = (self.prefill_chunks_per_tick
                      if len(self._active) < self.n_slots // 2 else 1)
            for _ in range(rounds):
                self._prefill_step()

            idle = not self._active and not self._pf
            if ran_block or (idle and self._finished):
                # a block's span: dispatch -> everything flushed, so the
                # tick_ms percentiles include interleaved prefill work (the
                # interference being measured) but not host-side admission
                self.last_token.block_until_ready()
                if ran_block:
                    self.spans.close(block)
                    block_s.append(block.t1 - block.t0)
                self._drain(ticks)
                ran_block = False
            elif (queue and not self._active and not self._pf
                  and not self._finished):
                # pages exhausted by queued work that can never fit together;
                # admit rejected everything it could — avoid spinning
                req = queue.popleft()
                req.rejected = True
                req.done = True

        wall = time.time() - t0
        served = [r for r in requests if not r.rejected]
        toks = sum(len(r.out) for r in served)
        lat = sorted((r.finish_tick - r.arrival) for r in served
                     if r.finish_tick >= 0)
        per_tick = sorted(w / K for w in block_s)
        stats = {
            "engine": "paged",
            "requests": len(requests),
            "rejected": sum(r.rejected for r in requests),
            "tokens": toks,
            "ticks": ticks,
            "wall_s": wall,
            "tok_per_s": toks / max(wall, 1e-9),
            "p50_latency_ticks": _pct(lat, 0.50),
            "p99_latency_ticks": _pct(lat, 0.99),
            "tick_ms_p50": _pct(per_tick, 0.50) * 1e3,
            "tick_ms_p99": _pct(per_tick, 0.99) * 1e3,
            "prefill_stall_fraction": (
                self.stats_counters["stall_ticks"]
                / max(ticks + self.stats_counters["stall_ticks"], 1)),
            "page_utilization": self.kv.stats().utilization,
        }
        stats.update(self.stats_counters)
        return stats


def _pct(sorted_vals: list, q: float) -> float:
    if not sorted_vals:
        return 0.0
    i = min(len(sorted_vals) - 1, int(q * (len(sorted_vals) - 1) + 0.5))
    return float(sorted_vals[i])


# ---------------------------------------------------------------------------
# Dense reference batcher (benchmark baseline + correctness oracle)
# ---------------------------------------------------------------------------


class ContinuousBatcher:
    def __init__(self, model: LanguageModel, params, n_slots: int = 4,
                 max_len: int = 256, enc_len: int = 8):
        self.model = model
        self.params = params
        self.n_slots = n_slots
        self.max_len = max_len
        self.enc_len = enc_len
        self.cache = model.init_cache(n_slots, max_len, enc_len=enc_len)
        self._slot_specs = model.cache_specs(1, max_len, enc_len=enc_len)
        self.pos = np.zeros((n_slots,), np.int32)
        self.slot_req: list[Request | None] = [None] * n_slots
        self.last_token = np.zeros((n_slots,), np.int32)
        self._decode = jax.jit(model.decode_step)
        self._prefill = jax.jit(model.prefill)
        self._write_slot = jax.jit(self._write_slot_impl,
                                   static_argnames=("slot",))
        self.stats_counters = {"host_syncs": 0, "bytes_to_host": 0,
                               "bytes_to_device": 0}

    def _write_slot_impl(self, batched, single, *, slot: int):
        """Scatter a freshly-prefilled B=1 cache into slot `slot` of the
        batched cache.  The batch dim of every leaf is located via the cache
        spec's logical axes (scanned segments carry a leading layers dim)."""
        from repro.models.model import _is_spec_leaf

        def write(b, s_, spec):
            bdim = list(spec[1]).index("batch")
            idx = [slice(None)] * b.ndim
            idx[bdim] = slot
            src = jnp.take(s_, 0, axis=bdim)
            return b.at[tuple(idx)].set(src.astype(b.dtype))

        return jax.tree.map(
            lambda b, s_, spec: write(b, s_, spec), batched, single,
            self._slot_specs,
            is_leaf=lambda x: _is_spec_leaf(x) or not isinstance(x, dict))

    def admit(self, req: Request) -> bool:
        if len(req.prompt) + req.max_new + 1 > self.max_len:
            req.rejected = True
            req.done = True
            return True  # consumed (dropped), don't block the queue
        for s in range(self.n_slots):
            if self.slot_req[s] is None:
                self.slot_req[s] = req
                # real batched prefill into a B=1 cache, then slot-scatter —
                # the same `prefill` the dry-run's prefill cells lower
                cache1 = self.model.init_cache(1, self.max_len,
                                               enc_len=self.enc_len)
                tokens = jnp.asarray([req.prompt], jnp.int32)
                self.stats_counters["bytes_to_device"] += int(tokens.nbytes)
                logits, cache1 = self._prefill(self.params,
                                               {"tokens": tokens}, cache1)
                self.cache = self._write_slot(self.cache, cache1, slot=s)
                self.pos[s] = len(req.prompt)
                host_logits = np.asarray(logits)
                self.stats_counters["host_syncs"] += 1
                self.stats_counters["bytes_to_host"] += int(host_logits.nbytes)
                self.last_token[s] = int(np.argmax(host_logits[0]))
                return True
        return False

    def step(self) -> None:
        active = [s for s in range(self.n_slots) if self.slot_req[s]]
        if not active:
            return
        t = self.last_token.reshape(-1, 1).astype(np.int32)
        logits, self.cache = self._decode(self.params, jnp.asarray(t),
                                          self.cache, jnp.asarray(self.pos))
        self.stats_counters["bytes_to_device"] += t.nbytes + self.pos.nbytes
        nxt = np.asarray(jnp.argmax(logits, -1), np.int32)
        self.stats_counters["host_syncs"] += 1
        self.stats_counters["bytes_to_host"] += int(nxt.nbytes)
        for s in active:
            req = self.slot_req[s]
            req.out.append(int(t[s, 0]))
            self.pos[s] += 1
            self.last_token[s] = nxt[s]
            if (len(req.out) >= req.max_new
                    or self.pos[s] >= self.max_len - 1):
                req.done = True
                self.slot_req[s] = None

    def run(self, requests: list[Request]) -> dict:
        self.stats_counters = dict.fromkeys(self.stats_counters, 0)
        queue = collections.deque(requests)
        t0 = time.time()
        ticks = 0
        while queue or any(self.slot_req):
            # scan past non-admissible heads: a full pool stops the scan
            # (admit can only fail on capacity), but oversized requests are
            # consumed as rejected instead of wedging the queue forever
            n = len(queue)
            for _ in range(n):
                req = queue.popleft()
                if not self.admit(req):
                    queue.appendleft(req)
                    break
            self.step()
            ticks += 1
        wall = time.time() - t0
        served = [r for r in requests if not r.rejected]
        toks = sum(len(r.out) for r in served)
        stats = {"engine": "dense", "requests": len(requests),
                 "rejected": sum(r.rejected for r in requests),
                 "tokens": toks, "ticks": ticks, "wall_s": wall,
                 "tok_per_s": toks / max(wall, 1e-9)}
        stats.update(self.stats_counters)
        return stats


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="granite-moe-1b-a400m")
    ap.add_argument("--full", action="store_true",
                    help="use the registered full-width config, not the "
                         "smoke one")
    ap.add_argument("--engine", choices=("paged", "dense"), default="paged")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=256)
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--drain-every", type=int, default=8)
    ap.add_argument("--enc-len", type=int, default=8)
    args = ap.parse_args()

    enable_compile_cache()
    cfg = get_config(args.arch) if args.full else smoke_config(args.arch)
    model = LanguageModel(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(0))
    rng = np.random.RandomState(0)
    reqs = [Request(rid=i,
                    prompt=rng.randint(0, cfg.vocab_size, 8).tolist(),
                    max_new=args.max_new)
            for i in range(args.requests)]
    if args.engine == "paged":
        eng = PagedServingEngine(model, params, n_slots=args.slots,
                                 max_len=args.max_len,
                                 page_size=args.page_size,
                                 drain_every=args.drain_every,
                                 enc_len=args.enc_len)
        stats = eng.run(reqs)
    else:
        batcher = ContinuousBatcher(model, params, n_slots=args.slots,
                                    max_len=args.max_len,
                                    enc_len=args.enc_len)
        stats = batcher.run(reqs)
    print(f"[serve {args.arch}] {stats}")


if __name__ == "__main__":
    main()
