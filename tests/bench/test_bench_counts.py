"""The benchmark's operation and byte counts (the Llama architecture's,
``bench/arch/llama.py``), checked by hand and against the matmul arithmetic
of ``launch/flops.py``; its table of peaks."""
from __future__ import annotations

import pytest

from benchroot import REPO  # noqa: F401  (puts the repo on sys.path)

from bench import counts, peaks
from bench.arch import llama
from bench.counts import Work

# one layer, d 4, two query heads over one key/value head of 2, GLU of 3,
# vocabulary 5
TINY = {"num_hidden_layers": 1, "hidden_size": 4, "num_attention_heads": 2,
        "num_key_value_heads": 1, "head_dim": 2, "intermediate_size": 3,
        "num_local_experts": 0, "num_experts_per_tok": 0, "vocab_size": 5}


def test_tiny_prefill_round_by_hand():
    assert llama.attn_params(TINY) == 4 * 4 * 2 + 2 * 2 * 4  # q,k,v + o
    assert llama.expert_params(TINY) == 36
    # one member: positions 2 and 3 of its prompt, which ends here; the
    # queries attend to 3 and 4 keys
    w = llama.prefill_round(TINY, [(2, 2, True)])
    assert w.flops == 2 * 2 * (48 + 36) + 4 * 2 * 2 * (3 + 4) + 2 * 4 * 5
    kv_per_token = 1 * 2 * 1 * 2 * 2
    assert w.bytes == ((48 + 36) * 2 + 4 * kv_per_token + 2 * 4 * 2
                       + 4 * 5 * 2)
    # a member that does not finish its prompt computes no head
    assert llama.prefill_round(TINY, [(0, 2, False)]).flops == \
        2 * 2 * 84 + 4 * 2 * 2 * (1 + 2)


def test_tiny_decode_tick_by_hand():
    w = llama.decode_tick(TINY, [0, 5])
    assert w.flops == (2 * 84 + 4 * 2 * 2 * 1) + (2 * 84 + 4 * 2 * 2 * 6) \
        + 2 * 2 * 4 * 5
    assert w.bytes == 84 * 2 + 20 * 2 + (1 + 6) * 8 + 2 * 4 * 2
    # a block of three ticks: a slot that stops after one tick drops out
    ticks = counts.decode_block(llama, TINY, [(0, 1), (5, 3)])
    assert len(ticks) == 3 and ticks[0] == w
    assert ticks[1] == llama.decode_tick(TINY, [6])
    assert ticks[2] == llama.decode_tick(TINY, [7])


def test_expected_experts():
    m = {"num_local_experts": 32, "num_experts_per_tok": 8}
    assert llama.expected_experts(m, 1) == pytest.approx(8)
    assert llama.expected_experts(m, 2) == pytest.approx(32 * (1 - 0.75**2))
    assert llama.expected_experts(m, 512) == pytest.approx(32)


@pytest.mark.parametrize("arch,file", [
    ("granite-moe-1b-a400m", "granite-moe-1b-a400m.json"),
    ("deepseek-7b", "deepseek-7b-l6.json")])
def test_token_flops_match_launch_flops_matmul_arithmetic(arch, file):
    import json
    import os

    from repro.configs import get_config
    from repro.launch import flops as F

    # widths of the benchmark's configuration
    with open(os.path.join(REPO, "bench", "configs", file)) as f:
        m = json.load(f)["model"]
    cfg = get_config(arch).scaled(n_layers=m["num_hidden_layers"])
    mlp = F._moe_flops(cfg, 1) if cfg.n_experts else F._mlp_flops(cfg, 1)
    for keys in (1, 700):
        want = cfg.n_layers * (F._attn_flops(cfg, 1, keys) + mlp)
        assert llama.token_flops(m, keys) == pytest.approx(want)


def test_least_time_is_the_larger_bound():
    assert Work(197e12, 819e9 / 2).min_seconds(197e12, 819e9) == \
        pytest.approx(1.0)
    assert Work(1.0, 2 * 819e9).min_seconds(197e12, 819e9) == \
        pytest.approx(2.0)


def test_peaks_known_and_refused():
    v5e = peaks.peaks_for("TPU v5 lite")
    assert v5e.bf16_flops_per_s == 197e12 and v5e.hbm_bytes_per_s == 819e9
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks_for("TPU v9 imaginary")
    with pytest.raises(KeyError):
        peaks.peaks_for("cpu")
