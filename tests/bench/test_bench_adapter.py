"""The open loop on a smoke-size engine, on the CPU: due-time gating, the
window cut, counting, the result line, the refusal to run without a TPU,
and cells that later changes add as files alone."""
from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

from benchroot import SimClock, fixture, make_root, read_bench, \
    write_bench
from benchroot import run_bench as _bench

from bench import adapter, run, traffic  # noqa: E402

CONTRACT_KEYS = ["correct", "attempted", "failed", "metrics", "device",
                 "checks"]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("bench_adapter"))


def _loop(seed: int, seconds: float = 2.0, extra=()):
    cj = fixture("smoke-dense.json")
    mix = fixture("smoke_mix.json")
    tr = traffic.make_traffic(mix, seconds, seed, cj["model"]["vocab_size"])
    tr.requests.extend(extra)
    eng = adapter.make_engine(cj, seed)
    adapter.warm_up(eng)
    clock = SimClock()
    loop = adapter.OpenLoop(eng, tr, seconds, clock=clock, sleep=clock.sleep)
    loop.run()
    return loop, tr


def test_open_loop_gates_on_due_time_and_cuts_the_window():
    loop, tr = _loop(seed=11)
    assert loop.t_open is not None and loop.t_closed >= loop.t_end
    # the starting population was admitted before the window opened
    for p in tr.population:
        assert loop.stamps[p.rid].admit < loop.t_open
    admitted = 0
    for p in tr.requests:
        st = loop.stamps[p.rid]
        assert st.due == pytest.approx(loop.t_open + p.due_s)
        if st.admit is not None:
            admitted += 1
            assert st.admit >= st.due
        for t, n in st.deliveries:
            assert loop.t_open <= t <= loop.t_end and n > 0
    assert admitted > 0
    # the window ends without waiting for the requests in flight
    assert any(not r.done for r in loop.reqs)
    assert loop.tokens_in_window() == sum(
        n for st in loop.stamps.values() for _, n in st.deliveries)
    # the engine's methods are its own again once the loop has run
    assert loop.eng._admit.__func__ is type(loop.eng)._admit


def test_preroll_runs_before_the_window_and_ttft_is_followed_past_it():
    loop, tr = _loop(seed=13)
    assert tr.preroll and loop.preroll_s == 0.5
    for p in tr.preroll:
        st = loop.stamps[p.rid]
        assert -0.5 < p.due_s <= 0.0
        assert st.due == pytest.approx(loop.t_open + p.due_s)
        assert not st.deliveries or st.deliveries[0][0] >= loop.t_open
    # the loop served on past the window until every request due in it had
    # its first token, and no longer
    firsts = [loop.stamps[p.rid].first for p in tr.requests]
    assert None not in firsts and loop.unanswered() == 0
    assert loop.t_stop >= max(firsts) and loop.t_stop >= loop.t_end
    assert loop.t_stop < loop.t_end + traffic.FOLLOW_S
    ttft = loop.ttft_s()
    assert ttft == pytest.approx([f - loop.stamps[p.rid].due
                                  for f, p in zip(firsts, tr.requests)])
    # rounds and blocks are recorded inside the window only
    assert loop.blocks and loop.rounds


def test_steady_state_population_follows_the_rate():
    mix = fixture("smoke_mix.json")
    tr = traffic.make_traffic(mix, 2.0, 5, 256)
    assert len(tr.population) == round(4.0 * mix["decode_s"])
    assert len(tr.preroll) == round(4.0 * mix["preroll_s"])
    assert len(tr.requests) == 8
    faster = {**mix, "arrivals": {**mix["arrivals"], "rate_per_s": 8.0}}
    assert len(traffic.make_traffic(faster, 2.0, 5, 256).population) == 4
    # the slots a random moment finds busy hold the longer answers
    out = mix["output"]
    plain = traffic.quantiles(out, 1000)
    biased = traffic.quantiles(out, 1000, size_biased=True)
    assert biased.mean() == pytest.approx((plain ** 2).mean() / plain.mean(),
                                          rel=0.01)
    assert out["min"] <= biased.min() and biased.max() <= out["max"]


def test_attempted_and_failed_count_refused_requests():
    too_long = traffic.Planned(rid=999, due_s=0.0, prompt=[1] * 200,
                               max_new=4)  # over the 128-token slots
    loop, tr = _loop(seed=12, extra=[too_long])
    assert len(loop.reqs) == (len(tr.population) + len(tr.preroll)
                              + len(tr.requests))
    assert loop.failed == 1
    assert [r.rid for r in loop.reqs if r.rejected] == [999]


def test_traffic_is_the_same_work_in_another_order():
    mix = fixture("smoke_mix.json")
    a = traffic.make_traffic(mix, 10, 1, 256)
    b = traffic.make_traffic(mix, 10, 2**31 + 12345, 256)
    # the seed draws the token ids; sizes and arrivals keep one order
    for size in (lambda p: len(p.prompt), lambda p: p.max_new,
                 lambda p: p.due_s):
        for part in ("requests", "population", "preroll"):
            assert list(map(size, getattr(a, part))) == \
                list(map(size, getattr(b, part)))
    assert a.requests[-1].due_s == pytest.approx(b.requests[-1].due_s)
    assert [p.prompt for p in a.requests] != [p.prompt for p in b.requests]
    again = traffic.make_traffic(mix, 10, 1, 256)
    assert [p.prompt for p in a.requests] == [p.prompt for p in again.requests]


def test_result_line_holds_the_contract_keys(root):
    p = _bench(root, "--workload", "dense.smoke", "--seed", "3000000021",
               "--seconds", "2", "--trace", "0", "--cpu-rehearsal")
    assert p.returncode == 0, p.stderr[-2000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert list(out) == CONTRACT_KEYS
    assert set(out["device"]) == {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    names = [m["name"] for m in read_bench(root)["end_to_end"]]
    assert sorted(out["metrics"]) == sorted(names)
    for m in out["metrics"].values():
        assert set(m) == {"value", "unit"}
    assert all(set(c) == {"value", "limit"} for c in out["checks"].values())
    # the numbers compared are the last lines of standard error too
    tail = p.stderr.strip().splitlines()[-len(out["checks"]):]
    assert [line.split()[1] for line in tail] == list(out["checks"])


def test_refuses_to_run_without_a_tpu(root):
    p = _bench(root, "--workload", "dense.smoke", "--seed", "1",
               "--seconds", "1", "--trace", "0")
    assert p.returncode == run.NO_DEVICE
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_fails_without_the_program(tmp_path):
    """A checkout holding only BENCHMARK.json and the benchmark's own files
    has nothing to serve: no result, and a non-zero exit."""
    root = make_root(tmp_path)
    p = _bench(root, "--workload", "dense.smoke", "--seed", "1",
               "--seconds", "1", "--trace", "0", "--cpu-rehearsal",
               env_extra={"PYTHONPATH": ""})
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_new_mix_and_metric_are_found_by_name(tmp_path):
    root = make_root(tmp_path)
    with open(os.path.join(root, "bench", "traffic", "smoke_slow.json"),
              "w") as f:
        json.dump({"extends": "smoke_mix",
                   "arrivals": {"process": "gamma", "cv": 2.0,
                                "rate_per_s": 2.0}}, f)
    with open(os.path.join(root, "bench", "metrics",
                           "requests_finished.py"), "w") as f:
        f.write("def read(ctx):\n    return len(ctx.loop.finished())\n")
    bench = read_bench(root)
    bench["workloads"].append({"name": "dense.slow", "config": "smoke-dense",
                               "traffic": "smoke_slow", "chips": 1,
                               "why": "a cell added as data"})
    bench["end_to_end"].append({"name": "requests_finished",
                                "unit": "requests", "better": "higher",
                                "bound": 0.25, "source": "host_clock",
                                "workloads": ["dense.slow"]})
    write_bench(root, bench)
    out = run.run_cell(root, "dense.slow", seed=5, seconds=2.0, trace=False)
    assert out["metrics"]["requests_finished"]["value"] >= 1
    # population 2.0/s x decode_s, pre-roll 2.0/s x preroll_s, 2.0/s x 2 s
    assert out["attempted"] == 1 + 1 + 4
    other = run.run_cell(root, "dense.smoke", seed=5, seconds=2.0,
                         trace=False)
    assert "requests_finished" not in other["metrics"]
