"""Pallas kernel validation: shape/dtype sweeps, interpret-mode kernel vs the
pure-jnp oracle (assignment requirement: per-kernel allclose against ref.py)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.kernel_bench import ATTN_CONFIGS, WKV_CONFIGS
from repro.kernels import ops
from repro.kernels.ref import attention_ref, wkv_ref
from repro.models.recurrent import wkv_chunked


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-5, atol=2e-5)


ATTN_CASES = [
    # (B, Sq, Skv, Hq, Hkv, D, causal, window, dtype)
    (1, 128, 128, 2, 2, 64, True, 0, jnp.float32),
    (2, 256, 256, 4, 1, 64, True, 0, jnp.float32),   # MQA
    (2, 256, 256, 8, 2, 32, True, 0, jnp.float32),   # GQA 4:1
    (1, 128, 384, 2, 2, 64, True, 0, jnp.float32),   # q_offset continuation
    (1, 256, 256, 2, 2, 64, True, 128, jnp.float32),  # sliding window
    (1, 256, 256, 2, 1, 64, True, 64, jnp.float32),   # narrow window + MQA
    (1, 128, 128, 2, 2, 64, False, 0, jnp.float32),   # bidirectional (encoder)
    (2, 256, 256, 4, 4, 128, True, 0, jnp.bfloat16),
    (1, 384, 384, 2, 2, 256, True, 0, jnp.bfloat16),  # gemma head_dim
    (1, 256, 256, 4, 2, 80, True, 128, jnp.bfloat16),  # danube head_dim + SWA
]


@pytest.mark.parametrize("case", ATTN_CASES)
def test_flash_attention_vs_ref(case):
    B, Sq, Skv, Hq, Hkv, D, causal, window, dtype = case
    q_offset = Skv - Sq
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, Sq, Hq, D), dtype)
    k = jax.random.normal(ks[1], (B, Skv, Hkv, D), dtype)
    v = jax.random.normal(ks[2], (B, Skv, Hkv, D), dtype)
    out = ops.flash_attention(q, k, v, causal=causal, window=window,
                              q_offset=q_offset, interpret=True)
    ref = attention_ref(q, k, v, causal=causal, window=window,
                        q_offset=q_offset)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))


@pytest.mark.parametrize("block", [(64, 64), (128, 64), (64, 128)])
def test_flash_attention_block_shapes(block):
    bq, bk = block
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (1, 256, 2, 64), jnp.float32)
    k = jax.random.normal(ks[1], (1, 256, 2, 64), jnp.float32)
    v = jax.random.normal(ks[2], (1, 256, 2, 64), jnp.float32)
    from repro.kernels.flash_attention import flash_attention
    out = flash_attention(q, k, v, causal=True, block_q=bq, block_k=bk,
                          interpret=True)
    ref = attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


WKV_CASES = [
    # (B, S, H, N, chunk)
    (1, 64, 2, 16, 16),
    (2, 128, 2, 32, 32),
    (1, 128, 4, 64, 64),
    (2, 96, 2, 16, 32),  # chunk > remainder handling (96 % 32 == 0)
]


def _wkv_inputs(B, S, H, N, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    r = jax.random.normal(ks[0], (B, S, H, N), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, H, N), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, H, N), jnp.float32)
    # realistic decays: log_w = -exp(w_raw), w_raw in [-6, 0]
    w_raw = jax.random.uniform(ks[3], (B, S, H, N), jnp.float32, -6.0, 0.0)
    log_w = -jnp.exp(w_raw)
    u = jax.random.normal(ks[4], (H, N), jnp.float32) * 0.1
    s0 = jax.random.normal(ks[5], (B, H, N, N), jnp.float32) * 0.5
    return r, k, v, log_w, u, s0


@pytest.mark.parametrize("case", WKV_CASES)
def test_linear_scan_kernel_vs_ref(case):
    B, S, H, N, chunk = case
    r, k, v, log_w, u, s0 = _wkv_inputs(B, S, H, N)
    y, s_fin = ops.linear_scan(r, k, v, log_w, u, s0, chunk=chunk,
                               interpret=True)
    y_ref, s_ref = wkv_ref(r, k, v, log_w, u, s0)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(s_fin), np.asarray(s_ref),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("kernel", ["flash_attention", "linear_scan"])
def test_kernels_refuse_silent_interpret_off_tpu(kernel):
    """Off TPU, a kernel call that does not ask for interpret mode raises
    instead of quietly running the Pallas interpreter."""
    if jax.default_backend() == "tpu":
        pytest.skip("the compiled kernel is the default on TPU")
    if kernel == "flash_attention":
        x = jnp.zeros((1, 8, 1, 8), jnp.float32)
        args = (x, x, x)
    else:
        args = _wkv_inputs(1, 8, 1, 8)
    with pytest.raises(ValueError, match="interpret=True"):
        getattr(ops, kernel)(*args)


def test_wkv_chunked_xla_path_vs_ref():
    """The XLA chunked-parallel path used in model code must match the oracle."""
    r, k, v, log_w, u, s0 = _wkv_inputs(2, 160, 2, 32, seed=3)
    y, s_fin = wkv_chunked(r, k, v, log_w, u, s0, chunk=32)
    y_ref, s_ref = wkv_ref(r, k, v, log_w, u, s0)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(s_fin), np.asarray(s_ref),
                               rtol=1e-4, atol=1e-4)


PAD_ATTN_CASES = [
    # (B, Sq, Skv, Hq, Hkv, D, causal, window) — none divide the 64-tile
    (1, 100, 100, 2, 2, 64, True, 0),
    (1, 100, 100, 2, 2, 64, False, 0),   # bidirectional: kv_len mask is live
    (1, 72, 200, 2, 1, 64, True, 48),    # window + MQA + uneven q/k pads
]


@pytest.mark.parametrize("case", PAD_ATTN_CASES)
def test_flash_attention_padded_shapes_vs_ref(case):
    """Arbitrary (non-block-multiple) sequence lengths run through the
    pad-to-block / slice-back wrapper and must still match the oracle."""
    B, Sq, Skv, Hq, Hkv, D, causal, window = case
    q_offset = Skv - Sq if causal else 0
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    q = jax.random.normal(ks[0], (B, Sq, Hq, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, Skv, Hkv, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, Skv, Hkv, D), jnp.float32)
    out = ops.flash_attention(q, k, v, causal=causal, window=window,
                              q_offset=q_offset, block_q=64, block_k=64,
                              interpret=True)
    assert out.shape == q.shape
    ref = attention_ref(q, k, v, causal=causal, window=window,
                        q_offset=q_offset)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_linear_scan_padded_length_y_and_state_vs_ref():
    """S = 100 with chunk = 32 pads to 128; padded steps are identities for
    the recurrence (log_w = 0, k = 0), so both y and the final state must
    match the unpadded oracle."""
    r, k, v, log_w, u, s0 = _wkv_inputs(2, 100, 2, 32, seed=5)
    y, s_fin = ops.linear_scan(r, k, v, log_w, u, s0, chunk=32,
                               interpret=True)
    assert y.shape == r.shape
    y_ref, s_ref = wkv_ref(r, k, v, log_w, u, s0)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(s_fin), np.asarray(s_ref),
                               rtol=1e-4, atol=1e-4)


def test_flash_attention_fused_epilogue_vs_ref():
    """out_scale multiply + residual add are fused into the kernel epilogue;
    result must equal out_scale * ref + residual."""
    ks = jax.random.split(jax.random.PRNGKey(6), 4)
    q = jax.random.normal(ks[0], (1, 128, 2, 64), jnp.float32)
    k = jax.random.normal(ks[1], (1, 128, 2, 64), jnp.float32)
    v = jax.random.normal(ks[2], (1, 128, 2, 64), jnp.float32)
    res = jax.random.normal(ks[3], (1, 128, 2, 64), jnp.float32)
    out = ops.flash_attention(q, k, v, causal=True, out_scale=0.5,
                              residual=res, interpret=True)
    ref = 0.5 * attention_ref(q, k, v, causal=True) + res
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_attention_fused_epilogue_padded_vs_ref():
    """The residual rides through the pad/slice wrapper too."""
    ks = jax.random.split(jax.random.PRNGKey(7), 4)
    q = jax.random.normal(ks[0], (1, 100, 2, 64), jnp.float32)
    k = jax.random.normal(ks[1], (1, 100, 2, 64), jnp.float32)
    v = jax.random.normal(ks[2], (1, 100, 2, 64), jnp.float32)
    res = jax.random.normal(ks[3], (1, 100, 2, 64), jnp.float32)
    out = ops.flash_attention(q, k, v, causal=True, out_scale=2.0,
                              residual=res, block_q=64, block_k=64,
                              interpret=True)
    ref = 2.0 * attention_ref(q, k, v, causal=True) + res
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


# ------------------------------------------------- autotuner tile coverage
def _reachable_attention_tiles():
    """Every distinct (block_q, block_k) the tuner can pick across the 12
    kernel-bench configs' validated candidate sets."""
    from repro.kernels import autotune as at
    tiles = set()
    for c in ATTN_CONFIGS:
        for cand in at.attention_candidates(c["Sq"], c["Skv"], c["D"],
                                            c["Dv"], jnp.bfloat16):
            tiles.add((cand.block_q, cand.block_k))
    return sorted(tiles)


def test_reachable_attention_tiles_all_match_ref():
    """Union sweep: any tile the autotuner can select for any bench config
    must be numerically safe.  All reachable tiles are powers of two <= 512,
    so one S = 512 decoder shape exercises each exactly once."""
    tiles = _reachable_attention_tiles()
    assert len(tiles) >= 15  # the ladder really is being swept
    ks = jax.random.split(jax.random.PRNGKey(8), 3)
    q = jax.random.normal(ks[0], (1, 512, 2, 64), jnp.bfloat16)
    k = jax.random.normal(ks[1], (1, 512, 2, 64), jnp.bfloat16)
    v = jax.random.normal(ks[2], (1, 512, 2, 64), jnp.bfloat16)
    ref = np.asarray(attention_ref(q, k, v, causal=True), np.float32)
    for bq, bk in tiles:
        assert 512 % bq == 0 and 512 % bk == 0
        out = ops.flash_attention(q, k, v, causal=True, block_q=bq,
                                  block_k=bk, interpret=True)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), ref, **_tol(jnp.bfloat16),
            err_msg=f"tile ({bq}, {bk}) diverges from the oracle")


def _extreme_tiles(cands):
    by_area = sorted(cands, key=lambda c: (c.block_q * c.block_k, c.block_q))
    return {(t.block_q, t.block_k) for t in (by_area[0], by_area[-1])}


@pytest.mark.parametrize(
    "cfg", [pytest.param(c, id=c["name"]) for c in ATTN_CONFIGS])
def test_bench_config_extreme_tiles_vs_ref(cfg):
    """Per bench config (GQA ratios, MLA asymmetric head dims, windows):
    parity at the smallest and largest candidate tiles — the extremes
    bracket everything the tuner can return for that shape."""
    from repro.kernels import autotune as at
    cands = at.attention_candidates(cfg["Sq"], cfg["Skv"], cfg["D"],
                                    cfg["Dv"], jnp.bfloat16)
    assert cands, f"no candidates for {cfg['name']}"
    ks = jax.random.split(jax.random.PRNGKey(9), 3)
    q = jax.random.normal(ks[0], (cfg["B"], cfg["Sq"], cfg["Hq"], cfg["D"]),
                          jnp.bfloat16)
    k = jax.random.normal(ks[1], (cfg["B"], cfg["Skv"], cfg["Hkv"], cfg["D"]),
                          jnp.bfloat16)
    v = jax.random.normal(ks[2], (cfg["B"], cfg["Skv"], cfg["Hkv"],
                                  cfg["Dv"]), jnp.bfloat16)
    q_offset = cfg["Skv"] - cfg["Sq"] if cfg["causal"] else 0
    ref = np.asarray(attention_ref(q, k, v, causal=cfg["causal"],
                                   window=cfg["window"], q_offset=q_offset),
                     np.float32)
    for bq, bk in sorted(_extreme_tiles(cands)):
        out = ops.flash_attention(q, k, v, causal=cfg["causal"],
                                  window=cfg["window"], q_offset=q_offset,
                                  block_q=bq, block_k=bk, interpret=True)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), ref, **_tol(jnp.bfloat16),
            err_msg=f"{cfg['name']} tile ({bq}, {bk})")


def test_wkv_all_chunk_candidates_vs_ref():
    """Every chunk the tuner can pick for the bench WKV shape matches the
    oracle (including s_fin)."""
    from repro.kernels import autotune as at
    c = WKV_CONFIGS[0]
    cands = at.scan_candidates(c["S"], c["N"], jnp.float32)
    assert len(cands) >= 3
    r, k, v, log_w, u, s0 = _wkv_inputs(c["B"], c["S"], c["H"], c["N"],
                                        seed=7)
    y_ref, s_ref = wkv_ref(r, k, v, log_w, u, s0)
    for cand in cands:
        y, s_fin = ops.linear_scan(r, k, v, log_w, u, s0, chunk=cand.chunk,
                                   interpret=True)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   rtol=1e-4, atol=1e-4,
                                   err_msg=f"chunk {cand.chunk}")
        np.testing.assert_allclose(np.asarray(s_fin), np.asarray(s_ref),
                                   rtol=1e-4, atol=1e-4,
                                   err_msg=f"chunk {cand.chunk} s_fin")


def test_attention_core_vs_ref_banded():
    """models.attention.attention_core (banded SWA streaming) vs oracle."""
    from repro.models.attention import attention_core
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    B, S, H, D, W = 1, 4096, 2, 32, 256
    q = jax.random.normal(ks[0], (B, S, H, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, H, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, H, D), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    out = attention_core(q, k, v, pos, pos, causal=True, window=W)
    ref = attention_ref(q, k, v, causal=True, window=W)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
