"""Paged decode through a scanned segment reads and writes the stacked page
pools in place, at the scan's layer index.  These tests pin where its token
writes land (nowhere but the active slots' own pages, in every layer) and
that the served logits still equal the full forward."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import granite_moe_1b_a400m as granite
from repro.launch.paged_kv import PagedKVCache, decompose
from repro.models import LanguageModel
from repro.models.attention import ModelCtx

N_LAYERS = 3


@pytest.fixture(scope="module")
def granite3():
    cfg = granite.smoke().scaled(n_layers=N_LAYERS, compute_dtype="float32")
    model = LanguageModel(cfg)
    (seg,) = model.dec_segments
    assert seg.scanned and seg.repeats == N_LAYERS, seg
    return cfg, model, model.init(jax.random.PRNGKey(0))


def _full_logits(model, params, tokens):
    """(1, S, V) logits of the whole sequence at once (no cache)."""
    S = tokens.shape[1]
    pos = model._positions(1, S, None)
    ctx = ModelCtx(mode="train", positions=pos)
    x = model._embed(params, tokens)
    x, _, _ = model._backbone(params, x, None, ctx)
    return model._head(params, x)


def test_stacked_pool_writes_land_only_at_active_slots_pages(granite3):
    """One decode step over 3 scanned paged layers.  Slot 0 and 1 write one
    token each; slot 2 is inactive (pos == -1); slot 3 is active but its
    position falls on an unallocated logical page, whose table entry is the
    sentinel ``n_pages``.  Every layer's pools may change only at the two
    written ``(page, pos % page_size)``: a dropped write that wrapped into
    the next layer (``layer * n_pages + n_pages``) would show as a change at
    page 0 of layers 1 and 2."""
    cfg, model, params = granite3
    n_pages, ps = 8, 4
    S = n_pages  # the sentinel
    table = jnp.asarray([[0, 1, S], [2, 3, 4], [5, 6, S], [7, S, S]],
                        jnp.int32)
    pos = jnp.asarray([3, 6, -1, 5], jnp.int32)
    B = table.shape[0]
    written = {(0, 3), (3, 2)}  # (page, offset) of slots 0 and 1

    cache = model.init_cache(B, table.shape[1] * ps, dtype=jnp.float32,
                             pages=(n_pages, ps))
    pools = cache["seg0"]["sub0"]
    assert pools["k"].shape[:3] == (N_LAYERS, n_pages, ps)
    ks = jax.random.split(jax.random.PRNGKey(1), 2)
    before = {
        "k": jax.random.normal(ks[0], pools["k"].shape, jnp.float32),
        "v": jax.random.normal(ks[1], pools["v"].shape, jnp.float32),
        "pos": jnp.full(pools["pos"].shape, -1, jnp.int32),
    }
    cache = {"seg0": {"sub0": before}}
    toks = jnp.asarray([[1], [2], [3], [4]], jnp.int32)

    _, new = jax.jit(model.decode_step)(params, toks, cache, pos, table)
    after = new["seg0"]["sub0"]

    want = np.zeros((N_LAYERS, n_pages, ps), bool)
    for page, off in written:
        want[:, page, off] = True
    for name in ("k", "v", "pos"):
        old, cur = np.asarray(before[name]), np.asarray(after[name])
        changed = old != cur
        changed = changed.reshape(changed.shape[:3] + (-1,)).any(-1)
        np.testing.assert_array_equal(changed, want, err_msg=name)
    got_pos = np.asarray(after["pos"])
    for layer in range(N_LAYERS):
        assert got_pos[layer, 0, 3] == 3 and got_pos[layer, 3, 2] == 6
    # each layer wrote its own keys: the layers' projections differ
    k = np.asarray(after["k"])
    assert not np.allclose(k[0, 0, 3], k[1, 0, 3])


def test_paged_decode_over_stacked_pools_matches_full_forward(granite3):
    """Two requests of different lengths share a 3-slot pool (the middle
    slot idle) and decode together for several ticks through the scanned
    segment's stacked pools; each slot's logits equal its full forward."""
    cfg, model, params = granite3
    rng = np.random.RandomState(0)
    S = 20
    seqs = {0: rng.randint(0, cfg.vocab_size, (1, S)),
            2: rng.randint(0, cfg.vocab_size, (1, S))}
    prompt = {0: 9, 2: 5}
    ref = {s: np.asarray(_full_logits(model, params, jnp.asarray(t)))
           for s, t in seqs.items()}

    kv = PagedKVCache(model, n_slots=3, n_pages=12, page_size=4, max_pages=6,
                      dtype=jnp.float32)
    assert kv.alloc(1, 7) and kv.alloc(0, S) and kv.alloc(2, S)
    kv.free(1)  # slot 1's pages are dead; its table row is the sentinel
    for s, n in prompt.items():
        start = 0
        for c in decompose(n, 4):
            view = kv.gather_slot(s)
            _, view = model.prefill_chunk(
                params, {"tokens": jnp.asarray(seqs[s][:, start:start + c])},
                view, jnp.full((1,), start, jnp.int32))
            kv.scatter_slot(s, view)
            start += c

    step = jax.jit(model.decode_step)
    for tick in range(6):
        t = {s: n + tick for s, n in prompt.items()}
        toks = jnp.asarray([[seqs[0][0, t[0]]], [0], [seqs[2][0, t[2]]]],
                           jnp.int32)
        pos = jnp.asarray([t[0], -1, t[2]], jnp.int32)
        logits, kv.cache = step(params, toks, kv.cache, pos, table=kv.table)
        for s in seqs:
            np.testing.assert_allclose(
                np.asarray(logits[s]), ref[s][0, t[s]], rtol=3e-4, atol=3e-4,
                err_msg=f"slot {s}, decode tick {tick}")
