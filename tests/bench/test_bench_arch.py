"""Architectures are found by name (``bench/arch``): a new one is a file and
a configuration naming it; an unknown name fails at load, naming the file
it looked for.  The Llama module gives the numbers that the benchmark's
code gave before the architecture moved into a module of its own: counts,
weight bits, the program's parameter tree and the reference's logits,
pinned at granite's and deepseek-7b-l6's widths and at smoke size."""
from __future__ import annotations

import filecmp
import hashlib
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchroot import REPO, fixture, make_root, read_bench, run_bench, \
    write_bench

from bench import arch, counts, run
from bench import weights as W

STUB = '''"""A stand-in architecture: the Llama module's parts, each
saying when it is used."""
import sys

from bench.arch import llama


def _say(what):
    print(f"stub architecture: {what}", file=sys.stderr)


def program_config(cj):
    _say("program_config")
    return llama.program_config(cj)


def program_tree(model, m, key, served):
    _say("program_tree")
    return llama.program_tree(model, m, key, served)


layer_shapes = llama.layer_shapes
global_shapes = llama.global_shapes
prefill_round = llama.prefill_round
decode_tick = llama.decode_tick


class Reference(llama.Reference):
    def __init__(self, *args):
        _say("Reference")
        super().__init__(*args)
'''


def _add_cell(root: str, name: str, cj: dict) -> None:
    """A configuration file and a cell that uses it, as a later change
    would add them."""
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


def test_a_new_architecture_is_a_file(tmp_path):
    root = make_root(tmp_path)
    with open(os.path.join(root, "bench", "arch", "stub.py"), "w") as f:
        f.write(STUB)
    cj = {**fixture("smoke-dense.json"), "name": "smoke-stub",
          "architecture": "stub"}
    _add_cell(root, "stub.smoke", cj)
    # no file that the benchmark has was edited
    for d, _, files in os.walk(os.path.join(REPO, "bench")):
        for name in files:
            if name.endswith(".py"):
                mine = os.path.join(d, name)
                theirs = os.path.join(root, os.path.relpath(mine, REPO))
                assert filecmp.cmp(mine, theirs, shallow=False), mine
    p = run_bench(root, "--workload", "stub.smoke", "--seed", "3000000031",
                  "--seconds", "2", "--trace", "0", "--cpu-rehearsal")
    assert p.returncode == 0, p.stderr[-2000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["correct"], out["checks"]
    for part in ("program_config", "program_tree", "Reference"):
        assert f"stub architecture: {part}" in p.stderr


def test_an_unknown_architecture_names_the_missing_file(tmp_path):
    root = make_root(tmp_path)
    cj = {**fixture("smoke-dense.json"), "name": "smoke-nowhere",
          "architecture": "nowhere"}
    _add_cell(root, "nowhere.smoke", cj)
    want = os.path.join(root, "bench", "arch", "nowhere.py")
    with pytest.raises(ValueError,
                       match="there is no file " + re.escape(want)):
        run.load_cell(root, "nowhere.smoke")
    del cj["architecture"]
    with pytest.raises(ValueError, match="names no 'architecture'"):
        arch.of(cj)


# Numbers the benchmark's code gave before its Llama parts moved into
# bench/arch/llama.py, on the same inputs.
MEMBERS = [(0, 64, False), (128, 32, True), (1000, 64, True), (1900, 1, True)]
BLOCK = [(100, 8), (1500, 3), (0, 5)]
COUNTS = {
    "granite-moe-1b-a400m": (
        (129448003584.0, 2826231808.0),
        [(2729232384, 1728737280.0), (2729527296, 1728884736.0),
         (2729822208, 1729032192.0), (1725050880, 1315514368.0),
         (1725247488, 1315612672.0), (867637248, 862429184.0),
         (867735552, 862478336.0), (867833856, 862527488.0)]),
    "deepseek-7b-l6": (
        (400847241216.0, 3582173184.0),
        [(9959669760, 3424968704), (9959964672, 3425263616),
         (9960259584, 3425558528), (6545342464, 3277996032),
         (6545539072, 3278192640), (3277783040, 3277791232),
         (3277881344, 3277889536), (3277979648, 3277987840)]),
}


def _config(name: str) -> dict:
    with open(os.path.join(REPO, "bench", "configs", f"{name}.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", sorted(COUNTS))
def test_counts_are_the_parents(name):
    cj = _config(name)
    a, m = arch.of(cj), cj["model"]
    (flops, byts), ticks = COUNTS[name]
    assert a.prefill_round(m, MEMBERS) == counts.Work(flops, byts)
    assert counts.decode_block(a, m, BLOCK) == [counts.Work(*t)
                                                for t in ticks]


def _digest(tree: dict) -> str:
    h = hashlib.sha256()
    for k in sorted(tree):
        h.update(k.encode())
        h.update(np.asarray(tree[k]).tobytes())
    return h.hexdigest()[:16]


WEIGHTS = {  # layer (or "globals") -> bf16 bits, seed 2**31 + 12345
    "granite-moe-1b-a400m": {0: "23ad6ffdc6942817", 23: "73ade2d1505eeeab"},
    "smoke-dense": {0: "67b7f49bf18fa1c8", 1: "4e1331d768cc41b7",
                    "globals": "3d076fce17a82cd2"},
    "smoke-moe": {0: "7c3fe556ab0d9a57", 1: "aa108db6ffaaeb81",
                  "globals": "7a1af6a2cbe81a85"},
}


@pytest.mark.parametrize("name", sorted(WEIGHTS))
def test_weight_bits_are_the_parents(name):
    cj = _config(name) if name in COUNTS else fixture(f"{name}.json")
    a, m = arch.of(cj), cj["model"]
    key = W.seed_key(2**31 + 12345)
    bf = jnp.bfloat16
    for layer, want in WEIGHTS[name].items():
        if layer == "globals":
            got = W.global_weights(a.global_shapes(m), key, bf, bf)
        else:
            got = jax.jit(lambda k, i=layer: W.layer_weights(
                a.layer_shapes(m), k, i, bf, bf))(key)
        assert _digest(got) == want, layer


def test_deepseek_tensors_are_the_parents():
    """deepseek-7b-l6's bits follow from its shapes, which are pinned: the
    weights themselves (8.4 GB in float32) are too large to make here."""
    m = _config("deepseek-7b-l6")["model"]
    a = arch.load("llama")
    d, f = 4096 ** -0.5, 11008 ** -0.5
    assert a.layer_shapes(m) == {
        "attn_norm": ((4096,), 0.0), "wq": ((4096, 4096), d),
        "wk": ((4096, 4096), d), "wv": ((4096, 4096), d),
        "wo": ((4096, 4096), d), "mlp_norm": ((4096,), 0.0),
        "w_gate": ((4096, 11008), d), "w_up": ((4096, 11008), d),
        "w_down": ((11008, 4096), f)}
    assert a.global_shapes(m) == {
        "embed": ((102400, 4096), 0.02), "final_norm": ((4096,), 0.0),
        "lm_head": ((4096, 102400), 0.02)}


TREES = {"smoke-dense": "f67c791c19206ad1", "smoke-moe": "c366c8f93b133178"}


@pytest.mark.parametrize("name", sorted(TREES))
def test_program_tree_is_the_parents(name):
    from repro.models import LanguageModel

    cj = fixture(f"{name}.json")
    a = arch.of(cj)
    model = LanguageModel(a.program_config(cj))
    key = W.seed_key(2**33 + 7)
    tree = jax.jit(lambda k: a.program_tree(model, cj["model"], k,
                                            jnp.bfloat16))(key)
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        h.update(jax.tree_util.keystr(path).encode())
        h.update(np.asarray(leaf).tobytes())
    assert h.hexdigest()[:16] == TREES[name]


# logits at rows 34-39 of 40 tokens drawn from default_rng(0), seed
# 3000000021: (sum of the 6 x vocab logits, row 34's first four, argmax
# of each row), for the reference and its float8 control
LOGITS = {
    ("smoke-dense", False): (-5.0063166632608045, [
        -0.10214295983314514, 0.226558119058609, 0.11546529829502106,
        -0.12358357757329941], [61, 206, 90, 172, 211, 206]),
    ("smoke-dense", True): (-3.7994489422853803, [
        -0.1398303359746933, 0.25128117203712463, 0.11875040829181671,
        -0.10184022784233093], [61, 206, 90, 213, 211, 206]),
    ("smoke-moe", False): (10.465197748496394, [
        0.12341808527708054, -0.1500118523836136, -0.4110376834869385,
        -0.01989053748548031], [12, 151, 12, 141, 141, 141]),
    ("smoke-moe", True): (9.573869744301192, [
        0.12627732753753662, -0.12903650104999542, -0.3928568363189697,
        -0.007105565629899502], [12, 151, 12, 141, 141, 141]),
}


@pytest.mark.parametrize("name", ["smoke-dense", "smoke-moe"])
def test_reference_logits_are_the_parents(name):
    cj = fixture(f"{name}.json")
    m = cj["model"]
    ref = arch.of(cj).Reference(m, 3000000021, "bfloat16")
    tokens = np.zeros(128, np.int32)
    tokens[:40] = np.random.default_rng(0).integers(0, m["vocab_size"], 40)
    rows = np.zeros(128, np.int32)
    rows[:6] = np.arange(34, 40)
    for fp8 in (False, True):
        got = np.asarray(ref.logits(tokens, rows, fp8=fp8))[:6]
        total, first, argmax = LOGITS[name, fp8]
        # float32 sums in XLA's order: equal to the last bit on one
        # machine; the tolerance is for another thread count's order
        assert got.astype(np.float64).sum() == pytest.approx(total, rel=1e-6)
        assert got[0, :4].tolist() == pytest.approx(first, rel=1e-6)
        assert got.argmax(-1).tolist() == argmax
