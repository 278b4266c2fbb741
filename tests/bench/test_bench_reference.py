"""At smoke size on the CPU: what the engine serves through chunked paged
prefill and paged decode agrees with ``bench/reference`` for a dense and an
MoE configuration, and the float8 control fails the same comparison."""
from __future__ import annotations

import gc

import pytest

from benchroot import SimClock, fixture

from bench import adapter, correct, traffic

F32 = {"weights": "float32", "compute": "float32", "kv_cache": "float32"}
# float32 end to end on both sides: only the order of sums differs, so the
# served token is the reference's best save for ties closer than this
F32_GAP = 1e-3


def _served(cfg: str, seed: int, dtype=None, seconds: float = 2.0):
    cj = fixture(f"{cfg}.json")
    if dtype:
        cj["dtype"] = dtype
    tr = traffic.make_traffic(fixture("smoke_mix.json"), seconds, seed,
                              cj["model"]["vocab_size"])
    eng = adapter.make_engine(cj, seed)
    adapter.warm_up(eng)
    clock = SimClock()
    loop = adapter.OpenLoop(eng, tr, seconds, clock=clock, sleep=clock.sleep)
    loop.run()
    finished = loop.finished()
    loop.eng = None
    del eng
    gc.collect()
    return cj, finished


@pytest.mark.parametrize("cfg", ["smoke-dense", "smoke-moe"])
def test_engine_agrees_with_reference_in_float32(cfg):
    cj, finished = _served(cfg, seed=21, dtype=F32)
    reqs = correct.sample(finished, 21)
    # prefill that took several chunks, and decode past a block boundary
    assert any(bin(len(r.prompt)).count("1") > 1 for r in reqs)
    assert any(len(r.out) > 8 for r in reqs)
    got = correct.compare(cj, 21, reqs, control=True)
    assert got["tokens_compared"] >= cj["correct"]["min_tokens_compared"]
    assert got["widest_logit_gap"] <= F32_GAP, got
    assert got["control_widest_logit_gap"] > F32_GAP, got


@pytest.mark.parametrize("seed", [31, 32])
def test_bf16_engine_passes_and_float8_control_fails(seed):
    """The dense smoke cell served in bf16, as the chip serves it, against
    its limit (smoke-dense.json), which the float8 control exceeds."""
    cj, finished = _served("smoke-dense", seed)
    got = correct.compare(cj, seed, correct.sample(finished, seed),
                          control=True)
    limit = cj["correct"]["max_logit_gap"]
    assert got["widest_logit_gap"] <= limit < \
        got["control_widest_logit_gap"], got


def test_sample_holds_the_longest():
    class R:
        def __init__(self, rid, p, o):
            self.rid, self.prompt, self.out = rid, [0] * p, [0] * o

    reqs = [R(i, 10, 50) for i in range(20)] + [R(99, 100, 60)]
    s = correct.sample(reqs, 3)
    assert s[0].rid == 99
    assert len(s) == correct.SAMPLE_REQUESTS == len({r.rid for r in s})
    assert correct.sample(reqs, 3) == s
    # the seed draws which of the others are compared
    assert {r.rid for r in correct.sample(reqs, 4)} != {r.rid for r in s}
    assert len(correct.sample(reqs[:5], 3)) == 5
