"""Whether a configuration's engine programs fit one v5e, without the chip.

    JAX_PLATFORMS=cpu python3 bench/fit.py \
        bench/configs/granite-moe-1b-a400m.json

Compiles the engine's decode block and its largest prefill chunk for a
described v5e (``jax.experimental.topologies``) at the file's sizes and
prints each program's argument and temporary bytes.  The compiler refuses a
program that does not fit the chip's HBM, as it would on the chip.
"""
from __future__ import annotations

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (ROOT, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402


def main(path: str) -> int:
    from jax.experimental import topologies

    from bench import arch
    from repro.launch.serve import PagedServingEngine
    from repro.models import LanguageModel

    with open(path) as f:
        cj = json.load(f)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    model = LanguageModel(arch.of(cj, ROOT).program_config(cj))
    e = cj["engine"]
    built = {}

    def build():
        built["eng"] = PagedServingEngine(
            model, None, n_slots=e["n_slots"], max_len=e["max_len"],
            page_size=e["page_size"], dtype=jnp.dtype(cj["dtype"]["kv_cache"]))

    jax.eval_shape(build)
    eng = built["eng"]

    def placed(tree):
        return jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=chip),
            tree)

    def sds(shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=chip)

    params = placed(model.abstract_params())
    cache, table = placed((eng.kv.cache, eng.kv.table))
    state = placed((eng.last_token, eng.pos, eng.remaining, eng.out_buf,
                    eng.out_cnt))
    G, c = eng.prefill_group, eng.chunk_max
    programs = {
        "tick_block": eng._tick_block.lower(params, cache, table, *state),
        "chunk": eng._chunk.lower(params, cache, table, sds((G,)),
                                  sds((G, c)), sds((G,)), None),
    }
    for name, lowered in programs.items():
        mem = lowered.compile().memory_analysis()
        print(json.dumps({"config": cj["name"], "program": name,
                          "argument_bytes": mem.argument_size_in_bytes,
                          "temp_bytes": mem.temp_size_in_bytes}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
