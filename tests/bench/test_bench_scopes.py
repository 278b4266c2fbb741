"""Device time per named scope (``bench/scopes.py``) and the readers that use
it or the engine's own spans and stamps, on a hand-made trace
(``fixtures/scoped_trace.txtpb``) and the compiled text of its two programs
below, with every number worked out by hand.

In the window (10-110 us), by operation, in ns:

* ``jit_tick_block``, two calls (36000 and, clipped at the window's end,
  15000): ``%while.4`` (the layer loop, not a leaf) twice; ``%fusion.1``
  (f32, ``attn``) 10000 + 10000; ``%dus.2`` (``kv_pool``) 20000 + 5000;
  ``%lt`` (the loop's condition, no scope) 1000; ``%argmax.3``
  (``sample``) 2000; ``%copy.6``, which the prefill chunk holds too with
  the same type, but only the decode block on operand ``%a``: 3000, no
  scope;
* ``jit_chunk``, one call of 30000: ``%fusion.1`` (bf16: the same name,
  another op, ``moe``) 15000; ``%gather.7`` (``kv_pool``) 10000;
  ``%copy.9``, which neither program holds: 1000, unmatched;
* ``%add.5``, which both programs hold with the same type and operands
  (``sample`` in one, no scope in the other): 4000, all of it to the chunk,
  whose 30000 its own operations leave 5000 short of, while the decode
  block's operations account for all of its 51000.
"""
from __future__ import annotations

import os
import types

import jax
import numpy as np
import pytest

from benchroot import FIXTURES, REPO

from bench import run, scopes, trace
from repro.launch.serve import Request
from repro.launch.spans import Span, Spans, hlo_ops

TICK_BLOCK = """\
HloModule jit_tick_block, is_scheduled=true

%fused_computation (p0: f32[4], p1: f32[4]) -> f32[4] {
  %p0 = f32[4]{0} parameter(0)
  %p1 = f32[4]{0} parameter(1)
  ROOT %m = f32[4]{0} multiply(%p0, %p1), metadata={op_name="jit(tick_block)/while/body/attn/mul"}
}

%body (arg: (s32[], f32[4])) -> (s32[], f32[4]) {
  %arg = (s32[], f32[4]{0}) parameter(0)
  %i = s32[] get-tuple-element(%arg), index=0
  %x = f32[4]{0} get-tuple-element(%arg), index=1
  %fusion.1 = f32[4]{0} fusion(%x, %x), kind=kLoop, calls=%fused_computation
  %dus.2 = f32[4]{0} dynamic-update-slice(%fusion.1, %x, %i), metadata={op_name="jit(tick_block)/while/body/attn/kv_pool/dynamic_update_slice"}
  ROOT %t = (s32[], f32[4]{0}) tuple(%i, %dus.2)
}

%cond (arg.1: (s32[], f32[4])) -> pred[] {
  %arg.1 = (s32[], f32[4]{0}) parameter(0)
  %j = s32[] get-tuple-element(%arg.1), index=0
  %c = s32[] constant(8)
  ROOT %lt = pred[] compare(%j, %c), direction=LT
}

ENTRY %main (a: f32[4]) -> f32[4] {
  %a = f32[4]{0} parameter(0)
  %argmax.3 = f32[4]{0} negate(%a), metadata={op_name="jit(tick_block)/sample/argmax"}
  %add.5 = f32[4]{0} add(%a, %a), metadata={op_name="jit(tick_block)/sample/add"}
  %copy.6 = f32[4]{0} copy(%a)
  %init = (s32[], f32[4]{0}) tuple(%a, %argmax.3)
  %while.4 = (s32[], f32[4]{0}) while(%init), condition=%cond, body=%body
  ROOT %out = f32[4]{0} get-tuple-element(%while.4), index=1
}
"""

CHUNK = """\
HloModule jit_chunk, is_scheduled=true

%fused_moe (q0: bf16[8,4]) -> bf16[8,4] {
  %q0 = bf16[8,4]{1,0} parameter(0)
  ROOT %e = bf16[8,4]{1,0} exponential(%q0), metadata={op_name="jit(chunk)/moe/exp"}
}

ENTRY %main (t: bf16[8,4], pool: bf16[64,4], rows: s32[8], a: f32[4]) -> bf16[8,4] {
  %t = bf16[8,4]{1,0} parameter(0)
  %pool = bf16[64,4]{1,0} parameter(1)
  %rows = s32[8]{0} parameter(2)
  %a = f32[4]{0} parameter(3)
  %fusion.1 = bf16[8,4]{1,0} fusion(%t), kind=kLoop, calls=%fused_moe
  %gather.7 = bf16[8,4]{1,0} gather(%pool, %rows), offset_dims={1}, metadata={op_name="jit(chunk)/kv_pool/gather"}
  %add.5 = f32[4]{0} add(%a, %a)
  %copy.6 = f32[4]{0} copy(%sum), metadata={op_name="jit(chunk)/kv_pool/copy"}
  ROOT %sum = bf16[8,4]{1,0} add(%fusion.1, %gather.7)
}
"""

T_OPEN, T_CLOSED = 100.0, 101.0


@pytest.fixture(scope="module")
def reduced():
    with open(os.path.join(FIXTURES, "scoped_trace.txtpb")) as f:
        return trace.reduce(jax.profiler.ProfileData.from_text_proto(f.read()))


def _engine():
    spans = Spans()
    for name, t0, t1, args in [
            ("decode_block", 99.5, 99.9, {"ticks": 8}),  # before the window
            ("decode_block", 100.1, 100.4, {"ticks": 8}),
            ("prefill_round", 100.2, 100.3, {"tokens": 24}),
            ("gc", 100.25, 100.253, {"generation": 0}),
            ("decode_block", 100.5, 100.9, {"ticks": 8}),
            ("prefill_round", 100.6, 100.7, {"tokens": 16}),
            ("gc", 100.8, 100.801, {"generation": 2}),
            ("decode_block", 101.0, 101.4, {"ticks": 8}),  # after it
            ("gc", 101.1, 101.2, {"generation": 2})]:
        spans.ring.append(Span(name, None, [], args, t0, t1))
    # the engine names its programs by function, a profile by jit_<function>
    programs = {"tick_block": [hlo_ops(TICK_BLOCK)],
                "chunk": [hlo_ops(CHUNK)]}
    return types.SimpleNamespace(spans=spans, hlo_ops=lambda: programs)


def _ctx(reduced, eng, reqs=()):
    loop = types.SimpleNamespace(
        eng=eng, t_open=T_OPEN, t_closed=T_CLOSED, t_end=T_CLOSED,
        t_stop=103.0, reqs=list(reqs), offsets={1: 0.1, 2: 0.2, 3: 0.3,
                                                4: 0.4, 5: 0.5},
        rounds=[], blocks=[])
    return run.Ctx(loop=loop, m={}, peaks=None, reduced=reduced, setup_s=1.0,
                   seconds=T_CLOSED - T_OPEN, drain_every=8)


def test_scope_ns_counts_leaves_once_and_splits_what_is_shared(reduced):
    got = scopes.scope_ns(reduced, {"jit_tick_block": [hlo_ops(TICK_BLOCK)],
                                    "jit_chunk": [hlo_ops(CHUNK)]})
    assert got.ns == {
        "jit_tick_block": {"attn": 20000, "kv_pool": 25000,
                           "other": 1000 + 3000, "sample": 2000},
        "jit_chunk": {"moe": 15000, "kv_pool": 10000, "other": 4000},
    }
    assert got.unmatched_ns == 1000
    assert got.program_ns("jit_tick_block") == \
        reduced.program_ns("jit_tick_block")
    # every leaf operation of the window is counted once: the ops' clipped
    # time less the loops'
    loops = 31000 + 15000
    assert got.program_ns("jit_tick_block") + got.program_ns("jit_chunk") \
        + got.unmatched_ns == sum(reduced.ops.values()) - loops


def test_scope_readers_on_the_trace(reduced):
    ctx = _ctx(reduced, _engine())

    def read(name):
        return run.read_metric(REPO, name, ctx)

    # 25000 ns of kv_pool and 20000 of attn over two blocks of 8 ticks
    assert read("decode_kv_pool_ms_per_tick") == pytest.approx(0.025 / 16)
    assert read("decode_attn_ms_per_tick") == pytest.approx(0.020 / 16)
    # 10000 ns of kv_pool and 15000 of moe over 24 + 16 prompt tokens
    assert read("prefill_kv_pool_ms_per_ktok") == pytest.approx(0.010 / 0.04)
    assert read("prefill_moe_ms_per_ktok") == pytest.approx(0.015 / 0.04)
    # 3 ms + 1 ms of collections over a 1-s window
    assert read("gc_pause_ms_per_s") == pytest.approx(4.0)


def _req(rid, t_admit, t_prefilled, rejected=False):
    r = Request(rid=rid, prompt=[1], max_new=1, rejected=rejected)
    r.t_admit, r.t_prefilled = t_admit, t_prefilled
    return r


def test_prefill_wait_reads_the_engines_stamps(reduced):
    reqs = [_req(1, 100.1, 100.2), _req(2, 100.3, 100.5),
            _req(3, 102.5, None),  # still prefilling: 103.0 - 102.5
            _req(4, None, None),  # never admitted: no prefill wait
            _req(5, 100.0, 109.0, rejected=True),
            _req(99, 90.0, 99.0)]  # not due in the window
    ctx = _ctx(None, None, reqs)
    got = run.read_metric(REPO, "prefill_wait_p95_ms", ctx)
    assert got == pytest.approx(float(np.percentile([0.1, 0.2, 0.5], 95))
                                * 1e3)


def test_readers_find_nothing_without_the_programs_record(reduced):
    """On a program that records no spans, stamps or scopes (as before
    they existed), and on a run with no trace, each reader returns None."""
    bare = types.SimpleNamespace()
    old_reqs = [types.SimpleNamespace(rid=1, rejected=False)]
    names = ("decode_kv_pool_ms_per_tick", "decode_attn_ms_per_tick",
             "prefill_kv_pool_ms_per_ktok", "prefill_moe_ms_per_ktok",
             "gc_pause_ms_per_s", "prefill_wait_p95_ms")
    for ctx in (_ctx(reduced, bare, old_reqs), _ctx(None, bare, old_reqs)):
        for name in names:
            assert run.read_metric(REPO, name, ctx) is None, name
    untraced = _ctx(None, _engine())
    for name in names[:4]:
        assert run.read_metric(REPO, name, untraced) is None, name
    assert run.read_metric(REPO, "gc_pause_ms_per_s", untraced) == \
        pytest.approx(4.0)
