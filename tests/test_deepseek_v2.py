"""DeepSeek-V2 on the serving engine, at smoke size on the CPU, against the
plain reference of ``bench/reference/deepseek_v2.py`` on seeded weights.

The fixture (``tests/bench/fixtures/smoke-dsv2.json``) has 8 experts in 4
groups, 2 chosen, top-3 (so that the third expert can fall in a group left
out), 4 held (experts 4-7), and YaRN over an original context
of 64 positions, so that the ramp falls inside its 4 rotary pairs.  The
program computes in float32 here (its weights are the served bfloat16
values), so the comparison can be tight: each mechanism left out of the
program must fail it.
"""
from __future__ import annotations

import hashlib
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (REPO, os.path.join(REPO, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import arch  # noqa: E402
from bench import weights as W  # noqa: E402
from bench.reference import deepseek_v2 as ref_mod  # noqa: E402
from repro.models import LanguageModel  # noqa: E402
from repro.models import layers as layers_mod  # noqa: E402
from repro.models import mla as mla_mod  # noqa: E402
from repro.models import moe as moe_mod  # noqa: E402

SEED = 3100000077
S = 40  # prompt 24 + 16 decoded positions
PROMPT = 24
# float32 on both sides; the engine's absorbed latent attention sums in
# another order than the reference's expanded heads, and its chunked prefill
# another again: the widest gap measured is 1.5e-6 of logits about 0.6 in
# size, so 1e-4 leaves room for other thread counts' orders and lies far
# under what each mechanism left out moves (0.56 to 0.91)
TOL = 1e-4


def _fixture() -> dict:
    with open(os.path.join(REPO, "tests", "bench", "fixtures",
                           "smoke-dsv2.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def setup():
    cj = _fixture()
    a = arch.of(cj)
    m = cj["model"]
    cfg = a.program_config(cj).scaled(compute_dtype="float32",
                                      param_dtype="float32")
    tree = jax.jit(lambda k: a.program_tree(LanguageModel(cfg), m, k,
                                            jnp.bfloat16))(W.seed_key(SEED))
    params = jax.tree.map(lambda t: t.astype(jnp.float32), tree)
    tokens = np.random.default_rng(5).integers(0, m["vocab_size"], S)
    ref = a.Reference(m, SEED, "bfloat16")
    padded = np.zeros(64, np.int32)
    padded[:S] = tokens
    want = np.asarray(ref.logits(padded, np.arange(64, dtype=np.int32)))[:S]
    return cfg, params, tokens, want


def _engine_logits(cfg, params, tokens) -> np.ndarray:
    """Logits at positions PROMPT-1 .. S-1: the prompt through the engine's
    chunked prefill program (one slot of a pool whose pages another slot
    holds too), then one decode step per token through the paged cache and
    its block table, as the decode block runs them."""
    from repro.launch.serve import PagedServingEngine

    eng = PagedServingEngine(LanguageModel(cfg), params, n_slots=2,
                             max_len=64, page_size=16, chunk_max=16,
                             prefill_group=2, dtype=jnp.float32)
    assert eng.kv.alloc(0, 20) and eng.kv.alloc(1, S + 1)
    start = 0
    logits = None
    for c in (16, 8):  # the engine's ladder cuts 24 into 16 + 8
        toks = np.zeros((2, c), np.int32)
        toks[0] = tokens[start:start + c]
        eng.kv.cache, logits = eng._chunk(
            params, eng.kv.cache, eng.kv.table, jnp.asarray([1, 2]),
            jnp.asarray(toks), jnp.asarray([start, -1]), None)
        start += c
    out = [np.asarray(logits[0])]
    step = jax.jit(eng.model.decode_step)
    cache = eng.kv.cache
    for t in range(PROMPT, S - 1):
        last = jnp.asarray([0, tokens[t]], jnp.int32)[:, None]
        pos = jnp.asarray([-1, t], jnp.int32)  # slot 0 idle
        lg, cache = step(params, last, cache, pos, table=eng.kv.table)
        out.append(np.asarray(lg[1]))
    return np.stack(out)


def _gap(setup) -> float:
    cfg, params, tokens, want = setup
    got = _engine_logits(cfg, params, tokens)
    return float(np.abs(got - want[PROMPT - 1:S - 1]).max())


def test_engine_prefill_and_decode_match_the_reference(setup):
    assert _gap(setup) < TOL


def _plain_freqs(cfg, rot_dim):
    exponent = jnp.arange(0, rot_dim, 2, dtype=jnp.float32) / rot_dim
    return 1.0 / (cfg.rope_theta ** exponent)


@pytest.mark.parametrize("omit", ["group_limit", "scaled_gates",
                                  "yarn_freqs", "mscale2"])
def test_a_program_without_each_mechanism_fails(setup, omit, monkeypatch):
    cfg, params, tokens, want = setup
    if omit == "group_limit":
        cfg = cfg.scaled(n_group=1, topk_group=1)
    elif omit == "scaled_gates":
        cfg = cfg.scaled(routed_scaling_factor=1.0)
    elif omit == "yarn_freqs":
        monkeypatch.setattr(layers_mod, "rope_freqs", _plain_freqs)
    else:
        monkeypatch.setattr(mla_mod, "softmax_scale", lambda c: (
            c.qk_nope_head_dim + c.qk_rope_head_dim) ** -0.5)
    assert _gap((cfg, params, tokens, want)) > 100 * TOL


# ---------------------------------------------------------------------------
# routing
# ---------------------------------------------------------------------------


def _hf_gate(x, w, n_group, topk_group, top_k, norm, factor):
    """DeepSeek-V2's MoEGate (group_limited_greedy, softmax), transcribed
    token by token in NumPy: (expert ids as a set, gate of each) per token."""
    out = []
    for row in x.astype(np.float64) @ w.astype(np.float64):
        scores = np.exp(row - row.max())
        scores /= scores.sum()
        groups = scores.reshape(n_group, -1)
        keep = np.argsort(-groups.max(-1))[:topk_group]
        masked = np.zeros_like(groups)
        masked[keep] = groups[keep]
        masked = masked.reshape(-1)
        idx = np.argsort(-masked)[:top_k]
        gates = masked[idx]
        gates = gates / (gates.sum() + 1e-20) if norm else gates * factor
        out.append(dict(zip(idx.tolist(), gates.tolist())))
    return out


@pytest.mark.parametrize("e,groups,chosen,k,norm", [
    (160, 8, 3, 6, False),  # DeepSeek-V2
    (8, 4, 2, 3, False),  # the smoke fixture
    (32, 1, 1, 8, True),  # granite: one group, renormalised
])
def test_routing_matches_hf_gate(e, groups, chosen, k, norm):
    from repro.configs import get_config

    cfg = get_config("deepseek-v2-236b").scaled(
        d_model=64, n_experts=e, top_k=k, n_group=groups, topk_group=chosen,
        norm_topk_prob=norm)
    rng = np.random.default_rng(e)
    x = rng.standard_normal((64, 64)).astype(np.float32)
    w = (rng.standard_normal((64, e)) * 0.5).astype(np.float32)
    gates, idx, _ = moe_mod._route(jnp.asarray(w), jnp.asarray(x), cfg)
    want = _hf_gate(x, w, groups, chosen, k, norm, cfg.routed_scaling_factor)
    for t, hf in enumerate(want):
        got = dict(zip(np.asarray(idx[t]).tolist(),
                       np.asarray(gates[t]).tolist()))
        assert set(got) == set(hf), t
        for i, g in hf.items():
            assert got[i] == pytest.approx(g, rel=1e-5), (t, i)


# ---------------------------------------------------------------------------
# one chip's share of the experts
# ---------------------------------------------------------------------------


def _shares(n_experts: int, held: int) -> list[tuple[int, int]]:
    return [(lo, held) for lo in range(0, n_experts, held)]


def test_program_shares_add_up_to_the_uncut_layer():
    """Every chip's held experts, each computing its part of the routed
    sum, plus the shared experts (which every chip computes alike) once,
    give the layer that holds all experts."""
    from repro.configs.deepseek_v2_236b import smoke
    from repro.models.layers import apply_mlp, init_mlp, split

    cfg = smoke().scaled(compute_dtype="float32")
    whole, _ = split(moe_mod.init_moe(jax.random.PRNGKey(1), cfg))
    shared, _ = split(init_mlp(jax.random.PRNGKey(2), cfg,
                               cfg.n_shared_experts * cfg.d_ff_expert))
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 12, cfg.d_model))
    y_shared = apply_mlp(shared, cfg, x)
    uncut = moe_mod.apply_moe(whole, cfg, x)[0] + y_shared
    total = y_shared
    for lo, n in _shares(cfg.n_experts, 2):
        part = {"router": whole["router"],
                **{k: whole[k][lo:lo + n] for k in ("w_gate", "w_up",
                                                    "w_down")}}
        total = total + moe_mod.apply_moe(
            part, cfg.scaled(held_experts=(lo, n)), x)[0]
    np.testing.assert_allclose(np.asarray(total), np.asarray(uncut),
                               rtol=1e-5, atol=1e-6)


def test_reference_shares_add_up_to_the_uncut_layer():
    m = _fixture()["model"]
    e = m["router_experts"]
    rng = np.random.default_rng(9)
    d, ff = m["hidden_size"], m["moe_intermediate_size"]
    sf = m["n_shared_experts"] * ff
    w = {"router": rng.standard_normal((d, e)) * d ** -0.5,
         "e_gate": rng.standard_normal((e, d, ff)) * d ** -0.5,
         "e_up": rng.standard_normal((e, d, ff)) * d ** -0.5,
         "e_down": rng.standard_normal((e, ff, d)) * ff ** -0.5,
         "s_gate": rng.standard_normal((d, sf)) * d ** -0.5,
         "s_up": rng.standard_normal((d, sf)) * d ** -0.5,
         "s_down": rng.standard_normal((sf, d)) * sf ** -0.5}
    w = {k: jnp.asarray(v, jnp.float32) for k, v in w.items()}
    a = jnp.asarray(rng.standard_normal((12, d)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        uncut = ref_mod._moe({**m, "n_routed_experts": e,
                              "first_held_expert": 0}, False, a, w)
        shared = ref_mod._glu(a, w["s_gate"], w["s_up"], w["s_down"], False)
        total = shared
        for lo, n in _shares(e, m["n_routed_experts"]):
            part = {**w, **{k: w[k][lo:lo + n]
                            for k in ("e_gate", "e_up", "e_down")}}
            total = total + ref_mod._moe(
                {**m, "first_held_expert": lo, "n_routed_experts": n},
                False, a, part) - shared
    np.testing.assert_allclose(np.asarray(total), np.asarray(uncut),
                               rtol=1e-5, atol=1e-5)


def test_yarn_ramp_of_the_published_config():
    """d = 64, theta 1e4, original 4096, beta 32/1: the ramp runs over
    pairs 10 to 23; below it the plain frequencies, above them / 40."""
    from repro.configs import get_config

    cfg = get_config("deepseek-v2-236b")
    assert layers_mod.yarn_correction_range(cfg, 64) == (10, 23)
    plain = np.asarray(_plain_freqs(cfg, 64))
    got = np.asarray(layers_mod.rope_freqs(cfg, 64))
    np.testing.assert_allclose(got[:11], plain[:11], rtol=1e-6)
    np.testing.assert_allclose(got[23:], plain[23:] / 40, rtol=1e-6)
    assert layers_mod.rope_magnitude(cfg) == 1.0
    assert mla_mod.softmax_scale(cfg) / 192 ** -0.5 == pytest.approx(
        1.58963, rel=1e-5)


# ---------------------------------------------------------------------------
# granite, which shares the MoE layer, computes what it computed
# ---------------------------------------------------------------------------

# sha256 of granite's smoke logits (full forward, chunked prefill, decode)
# as the program computed them before the held-experts layer and the
# group-limited routing
GRANITE_SMOKE_DIGEST = "d6a28cee81d52539"


def test_granite_smoke_logits_are_the_parents():
    from repro.configs import granite_moe_1b_a400m as granite
    from repro.models.attention import ModelCtx

    cfg = granite.smoke()
    model = LanguageModel(cfg)
    params = model.init(jax.random.PRNGKey(0))
    b, s = 2, 24
    tokens = jnp.asarray(np.random.RandomState(0).randint(
        0, cfg.vocab_size, (b, s)), jnp.int32)

    def full(p):
        ctx = ModelCtx(mode="train", positions=model._positions(b, s, None))
        x, _, _ = model._backbone(p, model._embed(p, tokens), None, ctx)
        return model._head(p, x)

    h = hashlib.sha256(np.asarray(jax.jit(full)(params)).tobytes())
    cache = model.init_cache(b, max_len=s, dtype=jnp.bfloat16)
    logits, cache = jax.jit(model.prefill_chunk)(
        params, {"tokens": tokens[:, :16]}, cache, jnp.zeros((b,), jnp.int32))
    h.update(np.asarray(logits).tobytes())
    step = jax.jit(model.decode_step)
    for t in range(16, s):
        logits, cache = step(params, tokens[:, t][:, None], cache,
                             jnp.full((b,), t, jnp.int32))
        h.update(np.asarray(logits).tobytes())
    assert h.hexdigest()[:16] == GRANITE_SMOKE_DIGEST
