"""DeepSeek-V2 in the benchmark: an architecture added as files.  Its counts
by hand at a small size; a smoke cell of it runs through ``bench/run.py``
with no file that the benchmark had edited; its configuration keeps the
published widths and cuts only depth and the experts held."""
from __future__ import annotations

import filecmp
import json
import os

import pytest

from benchroot import REPO, fixture, make_root, read_bench, run_bench, \
    write_bench

from bench import arch, counts, faults, run, traffic
from bench.arch import deepseek_v2 as dsv2

# two layers (the first dense), d 4, two heads, q rank 3, latent 2, nope 2,
# rope 2, value 2, dense GLU 5, expert GLU 3, 4 experts in the router of
# which 2 are held, top-2, one shared expert, vocabulary 7
TINY = {"num_hidden_layers": 2, "first_k_dense_replace": 1, "hidden_size": 4,
        "num_attention_heads": 2, "q_lora_rank": 3, "kv_lora_rank": 2,
        "qk_nope_head_dim": 2, "qk_rope_head_dim": 2, "v_head_dim": 2,
        "intermediate_size": 5, "moe_intermediate_size": 3,
        "router_experts": 4, "n_routed_experts": 2, "first_held_expert": 0,
        "num_experts_per_tok": 2, "n_shared_experts": 1, "vocab_size": 7}

# q_a 4x3, q_b 3x(2x4), kv_a 4x(2+2), kv_b 2x(2x4), o (2x2)x4
ATTN = 12 + 24 + 16 + 16 + 16
# per token and layer: the projections above, but in place of kv_b's
# expansion the absorptions of w_uk (2 heads x nope 2 x latent 2) and w_uv
# (2 x latent 2 x value 2)
ATTN_FLOPS = 2 * (ATTN - 16 + 8 + 8)
KEY_FLOPS = 2 * 2 * (2 * 2 + 2)  # scores over latent + rope, weighted latent
EXPERT = 3 * 4 * 3
# dense GLU; router over 4, top-2 at the held share 2/4, the shared expert
MLP_FLOPS = 2 * 3 * 4 * 5 + 2 * (4 * 4 + 2 * 0.5 * EXPERT + EXPERT)
HEAD = 4 * 7
LATENT = 2 * (2 + 2) * 2  # bytes a token holds over both layers


def test_tiny_decode_tick_by_hand():
    assert dsv2.attn_params(TINY) == ATTN
    assert dsv2.attn_flops(TINY, 0) == ATTN_FLOPS
    assert dsv2.key_flops(TINY) == KEY_FLOPS
    assert dsv2.mlp_flops(TINY) == MLP_FLOPS
    w = dsv2.decode_tick(TINY, [0, 5])
    assert w.flops == (2 * (2 * ATTN_FLOPS + (1 + 6) * KEY_FLOPS)
                       + 2 * (MLP_FLOPS + 2 * HEAD))
    # weights: both layers' attention, the dense GLU, the router, the
    # shared expert, and 2 x (1 - (1 - 2/4)^2) = 1.5 held experts hit by
    # two tokens; the head; 1 + 6 latent rows; two embedding rows
    weights = 2 * ATTN + 3 * 4 * 5 + 4 * 4 + EXPERT + 1.5 * EXPERT
    assert w.bytes == pytest.approx(weights * 2 + HEAD * 2 + 7 * LATENT
                                    + 2 * 4 * 2)
    ticks = counts.decode_block(dsv2, TINY, [(0, 1), (5, 2)])
    assert ticks == [w, dsv2.decode_tick(TINY, [6])]


def test_tiny_prefill_round_by_hand():
    # positions 2 and 3 of a prompt that ends here: 3 + 4 = 7 latent rows
    w = dsv2.prefill_round(TINY, [(2, 2, True)])
    assert w.flops == (2 * (2 * ATTN_FLOPS + 7 * KEY_FLOPS)
                       + 2 * MLP_FLOPS + 2 * HEAD)
    weights = 2 * ATTN + 3 * 4 * 5 + 4 * 4 + EXPERT + 1.5 * EXPERT
    assert w.bytes == pytest.approx(weights * 2 + 4 * LATENT + 2 * 4 * 2
                                    + HEAD * 2)
    # a member that does not finish its prompt computes no head
    assert dsv2.prefill_round(TINY, [(0, 2, False)]).flops == \
        2 * (2 * ATTN_FLOPS + 3 * KEY_FLOPS) + 2 * MLP_FLOPS


def _add_cell(root: str, name: str, cj: dict) -> None:
    rel = f"bench/configs/{cj['name']}.json"
    with open(os.path.join(root, rel), "w") as f:
        json.dump(cj, f)
    bench = read_bench(root)
    bench["configs"].append({"name": cj["name"], "source": cj["source"],
                             "file": rel, "reduced": cj["reduced"],
                             "why": "test"})
    bench["workloads"].append({"name": name, "config": cj["name"],
                               "traffic": "smoke_mix", "chips": 1,
                               "why": "test"})
    write_bench(root, bench)


def test_a_smoke_cell_runs_with_no_benchmark_file_edited(tmp_path):
    root = make_root(tmp_path)
    _add_cell(root, "dsv2.smoke", fixture("smoke-dsv2.json"))
    for d, _, files in os.walk(os.path.join(REPO, "bench")):
        for name in files:
            if name.endswith(".py"):
                mine = os.path.join(d, name)
                theirs = os.path.join(root, os.path.relpath(mine, REPO))
                assert filecmp.cmp(mine, theirs, shallow=False), mine
    p = run_bench(root, "--workload", "dsv2.smoke", "--seed", "3100000123",
                  "--seconds", "2", "--trace", "0", "--cpu-rehearsal")
    assert p.returncode == 0, p.stderr[-2000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["correct"], out["checks"]
    assert out["checks"]["tokens_compared"]["value"] >= 16


@pytest.mark.parametrize("fault,check", [
    (faults.altered_token, "mean_logit_gap"),
    (faults.cache_unchanged, "mean_logit_gap"),
    (faults.half_slots_silent, "unanswered")])
def test_planted_faults_are_not_correct(tmp_path, fault, check, monkeypatch):
    """The faults of ``bench/faults.py`` in the smoke cell's timed path: at
    smoke width only the mean gap separates a sound program from a broken
    one (the fixture's ``about_correct``), and it has to."""
    monkeypatch.setattr(traffic, "FOLLOW_S", 3.0)
    root = make_root(tmp_path)
    _add_cell(root, "dsv2.smoke", fixture("smoke-dsv2.json"))
    out = run.run_cell(root, "dsv2.smoke", seed=3100000124, seconds=2.0,
                       trace=False, setup_hook=fault)
    assert not out["correct"], out["checks"]
    c = out["checks"][check]
    assert c["value"] > c["limit"], out["checks"]


def _config() -> dict:
    with open(os.path.join(REPO, "bench", "configs",
                           "deepseek-v2-l5.json")) as f:
        return json.load(f)


def test_the_configuration_cuts_depth_and_experts_only():
    """The file holds the published config.json's keys at its top level,
    ``model`` the same (with the two keys of the cut), and every width as
    published: only the depth and the experts held differ."""
    cj = _config()
    m = cj["model"]
    for key, value in m.items():
        if key not in ("router_experts", "first_held_expert"):
            assert cj[key] == value, key
    assert cj["reduced"] == ["num_hidden_layers", "n_routed_experts"]
    assert cj["published"] == {"num_hidden_layers": 60,
                               "n_routed_experts": 160}
    assert (m["num_hidden_layers"], m["n_routed_experts"]) == (5, 20)
    assert m["router_experts"] == 160 and m["first_held_expert"] == 0
    cfg = arch.of(cj).program_config(cj)
    assert (cfg.n_experts, cfg.held_experts, cfg.n_group, cfg.topk_group,
            cfg.top_k) == (160, (0, 20), 8, 3, 6)
    assert (cfg.d_model, cfg.n_heads, cfg.q_lora_rank, cfg.kv_lora_rank,
            cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim,
            cfg.d_ff, cfg.d_ff_expert, cfg.vocab_size) == (
        5120, 128, 1536, 512, 128, 64, 128, 12288, 1536, 102400)
    # 8.13 GB of bfloat16 weights
    assert cfg.param_count() * 2 == pytest.approx(8.13e9, rel=1e-3)


def test_a_program_that_computes_another_block_is_refused():
    cj = _config()
    cj = {**cj, "model": {**cj["model"], "topk_method": "greedy"}}
    with pytest.raises(ValueError, match="topk_method"):
        arch.of(cj).program_config(cj)
    cj = _config()
    cj["reduced"] = ["num_hidden_layers"]
    with pytest.raises(ValueError, match="n_routed_experts"):
        arch.of(cj).program_config(cj)
