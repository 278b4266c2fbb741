"""The paged engine's own record of its loop: host spans (in memory and on
the profiler's host plane), request stamps, the collector's pauses, and the
named scopes of the programs it dispatches."""
import gc
import glob
import importlib
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.serve import PagedServingEngine, Request, _pct
from repro.launch.spans import SCOPES, hlo_ops
from repro.models import LanguageModel

LOOP_SPANS = {"admit", "prefill_round", "finalize", "decode_block", "drain",
              "fetch", "deliver"}


def _model(arch):
    mod = importlib.import_module(
        "repro.configs." + arch.replace("-", "_").replace(".", "_"))
    return LanguageModel(mod.smoke().scaled(compute_dtype="float32"))


def _requests(vocab, seed=3, rid0=0):
    rng = np.random.RandomState(seed)
    lens = [3, 9, 5, 13, 4, 11, 6]
    return [Request(rid=rid0 + i,
                    prompt=rng.randint(0, vocab, n).tolist(),
                    max_new=3 + (i % 4) * 2, arrival=2 * i)
            for i, n in enumerate(lens)]


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """granite's block at smoke size, served once with the profiler on."""
    model = _model("granite-moe-1b-a400m")
    params = model.init(jax.random.PRNGKey(0))
    eng = PagedServingEngine(model, params, n_slots=3, max_len=64,
                             page_size=8, chunk_max=8, drain_every=4,
                             dtype=jnp.float32)
    eng.run(_requests(model.cfg.vocab_size, rid0=100))  # compile first
    reqs = _requests(model.cfg.vocab_size)
    trace_dir = str(tmp_path_factory.mktemp("serve_trace"))
    mark = time.perf_counter()
    opts = jax.profiler.ProfileOptions()  # as the benchmark traces
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        stats = eng.run(reqs)
    finally:
        jax.profiler.stop_trace()
    spans = [s for s in eng.spans.ring if s.t0 >= mark]
    path, = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    return eng, reqs, stats, spans, jax.profiler.ProfileData.from_file(path)


def test_spans_nest_and_name_their_requests(served):
    eng, reqs, _, spans, _ = served
    loop = [s for s in spans if s.name != "gc"]
    assert {s.name for s in loop} == LOOP_SPANS
    parents = {"admit": {None}, "decode_block": {None}, "drain": {None},
               "prefill_round": {None, "decode_block"},
               "finalize": {"prefill_round"}, "fetch": {"drain"},
               "deliver": {"drain"}}
    for s in loop:
        assert s.parent in parents[s.name], s
        assert s.t0 <= s.t1
    rids = {r.rid for r in reqs}
    admitted = [rid for s in loop if s.name == "admit" for rid in s.rids]
    assert sorted(admitted) == sorted(rids)
    finals = [s.rids for s in loop if s.name == "finalize"]
    assert sorted(rid for (rid,) in finals) == sorted(rids)
    for s in loop:
        if s.name == "prefill_round":
            assert s.args["tokens"] == s.args["chunk"] * len(s.rids)
        if s.name == "decode_block":
            assert s.args["ticks"] == eng.drain_every
            assert s.args["active"] == len(s.rids) > 0


def test_request_stamps_and_counters(served):
    eng, reqs, stats, spans, _ = served
    admits = {rid: s for s in spans if s.name == "admit" for rid in s.rids}
    finals = {s.rids[0]: s for s in spans if s.name == "finalize"}
    for r in reqs:
        # each stamp is taken inside the span of its step
        assert admits[r.rid].t0 <= r.t_admit <= admits[r.rid].t1, r
        assert finals[r.rid].t0 <= r.t_prefilled <= finals[r.rid].t1, r
        assert r.t_admit <= r.t_prefilled, r
    # the rounds' tokens are the prompts' tokens, each computed once
    rounds = [s for s in spans if s.name == "prefill_round"]
    assert sum(s.args["tokens"] for s in rounds) == \
        sum(len(r.prompt) for r in reqs)
    assert stats["prefill_chunks"] == sum(len(s.rids) for s in rounds)
    # the tick percentiles are read from the decode_block spans
    per_tick = sorted((s.t1 - s.t0) / eng.drain_every for s in spans
                      if s.name == "decode_block")
    assert stats["tick_ms_p50"] == pytest.approx(_pct(per_tick, 0.5) * 1e3)
    assert stats["tick_ms_p99"] == pytest.approx(_pct(per_tick, 0.99) * 1e3)


def test_spans_are_on_the_profilers_host_plane(served):
    """Each span of the loop is an event of the host plane with the same
    name, the same enclosing span, and its length to within 1 ms."""
    _, _, _, spans, data = served
    loop = sorted((s for s in spans if s.name != "gc"), key=lambda s: s.t0)
    events = []
    for plane in data.planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            evs = [ev for ev in line.events if ev.name in LOOP_SPANS]
            for ev in evs:
                around = [o for o in evs if o is not ev
                          and o.start_ns <= ev.start_ns
                          and o.end_ns >= ev.end_ns]
                inner = min(around, key=lambda o: o.duration_ns,
                            default=None)
                events.append((ev.start_ns, ev.name,
                               inner.name if inner else None,
                               ev.duration_ns))
    events.sort()
    assert [e[1] for e in events] == [s.name for s in loop]
    for (_, _, parent, ns), s in zip(events, loop):
        assert parent == s.parent, s
        assert abs(ns / 1e9 - (s.t1 - s.t0)) < 1e-3, s


def test_a_collection_during_run_is_a_gc_span(served):
    eng, _, _, _, _ = served
    hooks = list(gc.callbacks)
    finalize = eng._finalize

    def collecting(*args):
        gc.collect()
        return finalize(*args)

    eng._finalize = collecting
    try:
        mark = time.perf_counter()
        eng.run(_requests(eng.model.cfg.vocab_size, rid0=200)[:2])
    finally:
        eng._finalize = finalize
    assert gc.callbacks == hooks  # the hook is gone again
    pauses = [s for s in eng.spans.ring if s.name == "gc" and s.t0 >= mark]
    ours = [s for s in pauses if s.args["generation"] == 2
            and s.parent == "finalize"]
    assert len(ours) == 2 and all(s.t1 > s.t0 for s in ours)
    # no collection outside run() is recorded
    mark = time.perf_counter()
    gc.collect()
    assert not [s for s in eng.spans.ring if s.t0 >= mark]


def test_an_exception_leaves_no_span_open(served):
    eng = served[0]
    step = eng._prefill_step

    def failing():
        if eng.spans._open:  # a round behind a decode block
            raise RuntimeError("stop")
        return step()

    eng._prefill_step = failing
    try:
        with pytest.raises(RuntimeError):
            eng.run(_requests(eng.model.cfg.vocab_size, rid0=300)[:2])
    finally:
        eng._prefill_step = step
    assert not eng.spans._open
    # the decode block the failure left open was closed, and recorded
    last = eng.spans.ring[-1]
    assert last.name == "decode_block" and last.t0 <= last.t1


def test_programs_carry_named_scopes_and_compile_nothing(served):
    eng = served[0]
    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda ev, *_, **__: compiles.append(ev)
        if ev == "/jax/core/compile/backend_compile_duration" else None)
    got = eng.hlo_ops()
    assert not compiles
    scopes = {p: {op.scope for ops in exes for op in ops}
              for p, exes in got.items()}
    assert scopes["tick_block"] >= {"embed", "attn", "kv_pool", "moe",
                                    "lm_head", "sample"}
    assert scopes["chunk"] >= {"embed", "attn", "kv_pool", "moe", "lm_head"}
    assert "sample" in scopes["finalize"]
    assert len(got["chunk"]) == len({k for p, k in eng._signatures
                                     if p == "chunk"}) > 1
    # the layer loop is not a leaf; the operations of its body are there
    assert any(not op.leaf for op in got["tick_block"][0])


@pytest.mark.parametrize("arch,scope", [
    ("deepseek-7b", "mlp"), ("rwkv6-1.6b", "recurrent"),
    ("minicpm3-4b", "mla"), ("granite-moe-1b-a400m", "moe")])
def test_layer_mixers_are_scoped(arch, scope):
    model = _model(arch)
    B, L = 2, 16
    cache = model.init_cache(B, L, dtype=jnp.float32)
    text = jax.jit(model.decode_step).lower(
        model.abstract_params(), jnp.zeros((B, 1), jnp.int32), cache,
        jnp.zeros((B,), jnp.int32)).as_text(debug_info=True)
    for name in ("embed", "lm_head", scope):
        # a scope opens an op's name, or follows an outer scope in it
        assert f'"{name}/' in text or f"/{name}/" in text, name


HLO = """\
HloModule jit_tick_block, is_scheduled=true

%fused_computation (p0: f32[4], p1: f32[4]) -> f32[4] {
  %p0 = f32[4]{0} parameter(0)
  %p1 = f32[4]{0} parameter(1)
  %m = f32[4]{0} multiply(%p0, %p1), metadata={op_name="jit(f)/while/body/attn/mul"}
  ROOT %b = f32[4]{0} bitcast(%m)
}

%body (arg: (s32[], f32[4])) -> (s32[], f32[4]) {
  %arg = (s32[], f32[4]{0}) parameter(0)
  %i = s32[] get-tuple-element(%arg), index=0
  %x = f32[4]{0} get-tuple-element(%arg), index=1
  %fusion.1 = f32[4]{0} fusion(%x, %x), kind=kLoop, calls=%fused_computation
  %dus.2 = f32[4]{0} dynamic-update-slice(%fusion.1, %x, %i), metadata={op_name="jit(f)/while/body/attn/kv_pool/dynamic_update_slice"}
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
  %argmax.3 = f32[4]{0} negate(%a), metadata={op_name="jit(f)/sample/argmax"}
  %init = (s32[], f32[4]{0}) tuple(%a, %argmax.3)
  %while.4 = (s32[], f32[4]{0}) while(%init), condition=%cond, body=%body, metadata={op_name="jit(f)/while"}
  ROOT %out = f32[4]{0} get-tuple-element(%while.4), index=1
}
"""


def test_hlo_ops_reads_scopes_out_of_a_compiled_program():
    ops = {op.name: op for op in hlo_ops(HLO)}
    # the fused computation's own instructions are not operations
    assert "m" not in ops and "b" not in ops and "p0" not in ops
    assert ops["fusion.1"].scope == "attn"  # from inside the fusion
    assert ops["dus.2"].scope == "kv_pool"  # the innermost scope
    assert ops["argmax.3"].scope == "sample"
    assert ops["while.4"].scope is None and not ops["while.4"].leaf
    assert ops["lt"].leaf and ops["lt"].scope is None  # the loop condition
    assert ops["dus.2"].line.startswith(
        "%dus.2 = f32[4]{0} dynamic-update-slice(")
    assert set(SCOPES) >= {op.scope for op in ops.values()} - {None}
