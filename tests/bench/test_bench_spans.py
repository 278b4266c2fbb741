"""The engine's own record of a run of the open loop agrees with the
adapter's view of the same run: its ``prefill_round`` and ``decode_block``
spans with ``loop.rounds`` and ``loop.blocks``, and ``Request.t_admit``
with the adapter's admission stamps.  Both read ``time.perf_counter()``."""
from __future__ import annotations

import time

import pytest

from benchroot import fixture

from bench import adapter, traffic


@pytest.fixture(scope="module")
def ran():
    cj = fixture("smoke-dense.json")
    tr = traffic.make_traffic(fixture("smoke_mix.json"), 2.0, 11,
                              cj["model"]["vocab_size"])
    eng = adapter.make_engine(cj, 11)
    adapter.warm_up(eng)
    mark = time.perf_counter()
    loop = adapter.OpenLoop(eng, tr, 2.0)
    loop.run()
    return loop, [s for s in eng.spans.ring if s.t0 >= mark]


def _run_of(loop, spans, records, same):
    """Where ``records`` sits in ``spans`` as one contiguous run, in order
    (of several places that fit, the one nearest the window's opening)."""
    n = len(records)
    at = [i for i in range(len(spans) - n + 1)
          if all(same(s, r) for s, r in zip(spans[i:i + n], records))]
    assert at, "the adapter's records are no run of the engine's spans"
    return min(at, key=lambda i: abs(spans[i].t0 - loop.t_open))


def _agrees_within_the_window(loop, spans, first, n):
    """The spans that start inside the window are the adapter's run, but
    for one at either end that started within a millisecond of the window's
    edge (the adapter decides at its own clock reading beside the span's)."""
    inside = [i for i, s in enumerate(spans)
              if loop.t_open <= s.t0 < loop.t_closed]
    extra = set(inside) ^ set(range(first, first + n))
    for i in extra:
        edge = min(abs(spans[i].t0 - loop.t_open),
                   abs(spans[i].t0 - loop.t_closed))
        assert edge < 1e-3, (spans[i], loop.t_open, loop.t_closed)
    assert len(extra) <= 2


def test_prefill_round_spans_are_the_adapters_rounds(ran):
    loop, spans = ran
    rounds = [s for s in spans if s.name == "prefill_round"]
    assert loop.rounds

    def same(span, members):
        return (len(span.rids) == len(members)
                and span.args["tokens"] == sum(c for _, c, _ in members))

    first = _run_of(loop, rounds, loop.rounds, same)
    _agrees_within_the_window(loop, rounds, first, len(loop.rounds))


def test_decode_block_spans_are_the_adapters_blocks(ran):
    loop, spans = ran
    blocks = [s for s in spans if s.name == "decode_block"]
    assert loop.blocks

    def same(span, block):
        return span.args["active"] == len(block)

    first = _run_of(loop, blocks, loop.blocks, same)
    _agrees_within_the_window(loop, blocks, first, len(loop.blocks))
    for span, block in zip(blocks[first:], loop.blocks):
        assert all(0 < ticks <= span.args["ticks"] for _, ticks in block)


def test_admission_stamps_agree(ran):
    loop, spans = ran
    admits = {rid: s for s in spans if s.name == "admit" for rid in s.rids}
    stamped = [r for r in loop.reqs if loop.stamps[r.rid].admit is not None
               and r.rid not in loop.population]
    assert stamped
    for r in stamped:
        span = admits[r.rid]
        # the engine stamps inside its admit span; the adapter reads its
        # clock once the engine's _admit has returned
        assert span.t0 <= r.t_admit <= span.t1
        assert span.t1 <= loop.stamps[r.rid].admit < span.t1 + 0.01
