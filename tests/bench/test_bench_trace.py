"""The trace reduction and the per-layer readers on a small hand-made trace
(``fixtures/small_trace.txtpb``), with every number worked out by hand.

Times in the fixture, in ns from the window's opening: ``jit_finalize``
runs from -4000 to 2000, ``jit_tick_block`` 5000-40000 and 70000-95000,
``jit_chunk`` 45000-60000, in a window of 100000.  Host spans: ``admit``
1500-5500, ``decode_block`` 4500-5200, ``drain`` 39000-44000,
``idle_wait`` 61000-69000."""
from __future__ import annotations

import os
import types

import jax
import pytest

from benchroot import FIXTURES, REPO

from bench import arch, run, trace


@pytest.fixture(scope="module")
def reduced():
    with open(os.path.join(FIXTURES, "small_trace.txtpb")) as f:
        return trace.reduce(jax.profiler.ProfileData.from_text_proto(f.read()))


def test_window_and_busy_union(reduced):
    assert reduced.window_ns == 100000
    # ops clipped to the window: 2000 + 35000 + 15000 + 25000
    assert reduced.busy_ns == 77000
    assert reduced.n_devices == 1


def test_per_program_time(reduced):
    assert reduced.programs == {"jit_finalize": (2000, 1),
                                "jit_tick_block": (60000, 2),
                                "jit_chunk": (15000, 1)}
    assert reduced.ops == {"fusion.1": 2000, "fusion.2": 40000,
                           "copy.3": 20000, "convolution.4": 15000}


def test_gap_labels(reduced):
    # each idle gap goes to the host span that overlaps it most; the last
    # one falls in no span
    assert reduced.gaps == [("admit", 3000), ("drain", 5000),
                            ("idle_wait", 10000), ("host", 5000)]
    b = trace.breakdown(reduced)
    assert b["idle_gaps"][0] == ["idle_wait", 1e-5]
    assert b["device_ops"][0] == ["fusion.2", 4e-5]


def test_readers_on_the_trace(reduced):
    m = {"num_hidden_layers": 1, "hidden_size": 4, "num_attention_heads": 2,
         "num_key_value_heads": 1, "head_dim": 2, "intermediate_size": 3,
         "num_local_experts": 0, "num_experts_per_tok": 0, "vocab_size": 5}
    loop = types.SimpleNamespace(rounds=[[(0, 4, False)], [(4, 1, True)]],
                                 blocks=[[(5, 8)], [(13, 2)]])
    peaks = types.SimpleNamespace(bf16_flops_per_s=1e12,
                                  hbm_bytes_per_s=1e9)
    ctx = run.Ctx(loop=loop, m=m, peaks=peaks, reduced=reduced, setup_s=1.0,
                  seconds=1e-4, drain_every=8, arch=arch.load("llama"))

    def read(name):
        return run.read_metric(REPO, name, ctx)

    assert read("device_idle_share") == pytest.approx(23.0)
    # (15000 + 2000) ns over 5 prompt tokens
    assert read("prefill_ms_per_ktok") == pytest.approx(0.017 / 0.005)
    # 60000 ns over two blocks of 8 ticks
    assert read("decode_tick_ms") == pytest.approx(0.06 / 16)
    for name in ("prefill_roofline", "decode_tick_roofline", "mfu.prefill",
                 "mfu.decode"):
        assert 0 < read(name) < 100
    none = run.Ctx(loop=types.SimpleNamespace(rounds=[], blocks=[]), m=m,
                   peaks=peaks, reduced=None, setup_s=1.0, seconds=1.0,
                   drain_every=8)
    for name in ("prefill_roofline", "decode_tick_roofline", "mfu.prefill",
                 "mfu.decode", "prefill_ms_per_ktok",
                 "decode_tick_ms", "device_idle_share"):
        assert run.read_metric(REPO, name, none) is None
