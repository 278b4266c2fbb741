"""Compile for a described TPU v5e, with no chip attached.

The TPU compiler is installed with jax, so the Pallas kernels and the
serving decode step compile here for a chip that is only described.  That
catches what interpret mode cannot: block shapes Mosaic cannot tile,
primitives it cannot lower, and programs that do not fit the chip's 16 GB.
Nothing runs, so these tests say nothing about results or speed.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.  Keep these tests in this one file.
"""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import flash_attention as fa
from repro.kernels import linear_scan as ls
from repro.launch.serve import PagedServingEngine
from repro.models import LanguageModel
from repro.models.model import _is_spec_leaf

V5E_HBM_BYTES = 16 * 1024**3


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # else libtpu logs under /tmp
        # a compile for a described chip is written to the persistent cache
        # but cannot be read back without one: keep the cache out of it
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        try:
            try:
                t = topologies.get_topology_desc(platform="tpu",
                                                 topology_name="v5e:2x2")
            except Exception as e:
                pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
            yield t
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("head_dim", [64, 80])
def test_flash_attention_compiles_for_v5e(one_chip, head_dim):
    """granite's GQA layout (16 query heads over 8 KV heads), at the
    published head_dim 64 and at 80, a width that is not a lane multiple."""
    B, S, Hq, Hkv = 2, 1024, 16, 8
    q = _sds((B, S, Hq, head_dim), jnp.bfloat16, one_chip)
    kv = _sds((B, S, Hkv, head_dim), jnp.bfloat16, one_chip)
    compiled = jax.jit(lambda q, k, v: fa.flash_attention(
        q, k, v, causal=True)).lower(q, kv, kv).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_wkv_compiles_for_v5e(one_chip):
    """rwkv6's head size N = 64, with a length that needs padding."""
    B, S, H, N = 2, 300, 32, 64
    seq = _sds((B, S, H, N), jnp.float32, one_chip)
    compiled = jax.jit(ls.linear_scan).lower(
        seq, seq, seq, seq, _sds((H, N), jnp.float32, one_chip),
        _sds((B, H, N, N), jnp.float32, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_granite_paged_decode_step_compiles_for_v5e(one_chip):
    """The serving engine's decode step for granite-moe-1b-a400m at full
    width and depth, over a paged pool of 32 slots x 2048 tokens, fits one
    v5e chip."""
    B, max_len, page_size = 32, 2048, 16
    max_pages = max_len // page_size
    model = LanguageModel(get_config("granite-moe-1b-a400m"))

    def placed(tree):
        return jax.tree.map(
            lambda s: _sds(s.shape, s.dtype, one_chip), tree)

    params = placed(model.abstract_params())
    specs = model.cache_specs(B, max_len, pages=(B * max_pages, page_size))
    cache = jax.tree.map(lambda leaf: _sds(leaf[0].shape, leaf[0].dtype,
                                           one_chip), specs,
                         is_leaf=_is_spec_leaf)
    tokens = _sds((B, 1), jnp.int32, one_chip)
    pos = _sds((B,), jnp.int32, one_chip)
    table = _sds((B, max_pages), jnp.int32, one_chip)
    compiled = jax.jit(model.decode_step).lower(
        params, tokens, cache, pos, table).compile()
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert 0 < used < V5E_HBM_BYTES, used


def _granite_engine_program(one_chip, program):
    """The serving engine's ``tick_block`` or its largest ``chunk``, lowered
    for one described v5e as ``chip_smoke.py`` serves granite (bf16
    weights, 32 slots x 2048 tokens, pages of 16), and the engine.  The
    engine is built under ``jax.eval_shape``, so its page pool exists only
    as shapes."""
    cfg = get_config("granite-moe-1b-a400m").scaled(param_dtype="bfloat16")
    model = LanguageModel(cfg)
    built = {}

    def build():
        built["eng"] = PagedServingEngine(model, None, n_slots=32,
                                          max_len=2048, page_size=16)

    jax.eval_shape(build)
    eng = built["eng"]

    def placed(tree):
        return jax.tree.map(lambda s: _sds(s.shape, s.dtype, one_chip), tree)

    params = placed(model.abstract_params())
    cache, table = placed((eng.kv.cache, eng.kv.table))
    if program == "tick_block":
        state = placed((eng.last_token, eng.pos, eng.remaining, eng.out_buf,
                        eng.out_cnt))
        return eng._tick_block.lower(params, cache, table, *state), eng
    G, c = eng.prefill_group, eng.chunk_max
    return eng._chunk.lower(
        params, cache, table, _sds((G,), jnp.int32, one_chip),
        _sds((G, c), jnp.int32, one_chip),
        _sds((G,), jnp.int32, one_chip), None), eng


@pytest.mark.parametrize("program", ["tick_block", "chunk"])
def test_granite_serving_engine_programs_fit_v5e(one_chip, program):
    """The engine's own decode block and its largest prefill chunk; the
    compiler refuses a program that does not fit the chip's HBM."""
    lowered, _ = _granite_engine_program(one_chip, program)
    mem = lowered.compile().memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert 0 < used < V5E_HBM_BYTES, used


#: temp bytes of granite's ``tick_block`` when each decode layer copied its
#: keys and values out of the stacked pools and wrote them back
SLICED_TICK_BLOCK_TEMP_BYTES = 6718960640


def test_granite_tick_block_decodes_in_place_in_stacked_pools(one_chip):
    """The decode block reads and writes granite's stacked page pools,
    ``bf16[24, 4096, 16, 512]``, at each layer in place: the token is
    scattered into the stacked pool, and no dynamic-slice or
    dynamic-update-slice copies one layer of a pool or a whole one (the
    copy out and back that took half of every decode tick), nor does a
    copy relayout a whole pool at the program's edge; so the block needs
    less temp memory than that copy did."""
    lowered, eng = _granite_engine_program(one_chip, "tick_block")
    pool = eng.kv.cache["seg0"]["sub0"]["k"].shape
    assert pool[0] == 24, pool
    layer = ",".join(map(str, pool[1:]))
    compiled = lowered.compile()
    hlo = compiled.as_text()
    assert re.search(rf"= bf16\[{pool[0]},{layer}\]\S* scatter\(", hlo)
    slices = re.findall(rf"= bf16\[(?:\d+,)?{layer}\]\S* "
                        rf"(?:dynamic-slice|dynamic-update-slice)\(", hlo)
    assert not slices, slices
    copies = re.findall(rf"= bf16\[{pool[0]},{layer}\]\S* copy\(", hlo)
    assert not copies, copies
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert 0 < temp < SLICED_TICK_BLOCK_TEMP_BYTES, temp
