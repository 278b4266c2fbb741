"""A whole run of the benchmark at smoke size on the CPU, with the timed path
broken underneath: ``correct`` has to come out false.  The faults a serving
cell can have (``bench/faults.py``): a token altered where the decode step
produces it, a decode step that hands back its key/value cache unchanged,
and half the slots left out of what the decode step hands back.  The
float8 control, put in the program's place, has to come out false too."""
from __future__ import annotations

import pytest

from benchroot import make_root

from bench import faults, run, traffic


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("bench_faults"))


def test_sound_run_is_correct(root):
    out = run.run_cell(root, "dense.smoke", seed=41, seconds=2.0,
                       trace=False)
    assert out["correct"], out["checks"]


def _over(checks: dict) -> list[str]:
    """The numbers compared that are on the wrong side of their limits."""
    return [k for k, c in checks.items()
            if (c["value"] < c["limit"] if k == "tokens_compared"
                else c["value"] > c["limit"])]


@pytest.mark.parametrize("fault", [
    pytest.param(faults.altered_token, id="_altered_token"),
    pytest.param(faults.cache_unchanged, id="_cache_unchanged"),
    pytest.param(faults.half_slots_silent, id="_half_slots_silent")])
def test_broken_decode_is_not_correct(root, fault, monkeypatch):
    # a silent slot's request never gets its first token: wait 3 s, not 60
    monkeypatch.setattr(traffic, "FOLLOW_S", 3.0)
    out = run.run_cell(root, "dense.smoke", seed=41, seconds=2.0,
                       trace=False, setup_hook=fault)
    assert not out["correct"], out["checks"]
    want = ("unanswered" if fault is faults.half_slots_silent
            else "widest_logit_gap")
    assert want in _over(out["checks"]), out["checks"]


def test_control_in_the_programs_place_is_not_correct(root):
    out = run.run_cell(root, "dense.smoke", seed=42, seconds=2.0,
                       trace=False, control=True)
    assert out["correct"], out["checks"]
    assert not out["control"]["correct"], out["control"]
    assert "widest_logit_gap" in _over(out["control"]["checks"])
    assert _over(out["checks"]) == []
