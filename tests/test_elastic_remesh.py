"""Elastic scaling: training survives losing half the pool.

Train sharded on a 4-device (2x2) mesh -> checkpoint -> restart on a 2-device
(2x1) mesh with resharded restore (CheckpointManager.restore(sharding_fn=...))
-> continue training.  Loss trajectory must match the single-mesh run
(the checkpoint is mesh-independent: host arrays + re-put under new
shardings).  Run in subprocesses (forced host device counts).
"""
import subprocess
import sys
import textwrap


def run_sub(code: str, devices: int, timeout: int = 560) -> str:
    prog = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = (
            "--xla_force_host_platform_device_count={devices}")
        {textwrap.indent(textwrap.dedent(code), '        ').strip()}
    """)
    # forced host devices are CPU devices: never let a child probe libtpu
    r = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                       text=True, timeout=timeout,
                       env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                            "JAX_PLATFORMS": "cpu"})
    assert r.returncode == 0, f"OUT:{r.stdout[-2000:]}\nERR:{r.stderr[-3000:]}"
    return r.stdout


TRAIN_SNIPPET = """
import jax, jax.numpy as jnp, numpy as np, json
from repro.configs.gemma_2b import smoke
from repro.models import LanguageModel
from repro.optim import AdamW, OptConfig
from repro.checkpoint import CheckpointManager
from repro.data import TokenDataset
from repro.distributed.sharding import MeshInfo, use_mesh_info
from repro.launch.mesh import make_mesh

def build():
    cfg = smoke().scaled(compute_dtype="float32")
    model = LanguageModel(cfg)
    opt = AdamW(OptConfig(peak_lr=3e-3, warmup_steps=2, decay_steps=20))
    data = TokenDataset(vocab_size=cfg.vocab_size, seq_len=32, global_batch=4)
    return cfg, model, opt, data

def step_fn(model, opt):
    def f(params, state, batch):
        (_, m), g = jax.value_and_grad(model.train_loss, has_aux=True)(
            params, batch)
        p2, s2, st = opt.update(g, state, params)
        return p2, s2, m["loss"]
    return jax.jit(f)
"""


def test_elastic_shrink_matches_straight_run(tmp_path):
    ck = str(tmp_path / "ck")
    # phase 1: 4 devices (2x2), 4 steps, save
    out1 = run_sub(TRAIN_SNIPPET + f"""
cfg, model, opt, data = build()
mesh = make_mesh((2, 2), ("data", "model"))
info = MeshInfo(mesh)
with use_mesh_info(info), mesh:
    params = jax.jit(model.init)(jax.random.PRNGKey(0))
    axes = model.param_axes
    shardings = jax.tree.map(lambda v, ax: info.sharding(v.shape, ax),
                             params, axes)
    params = jax.device_put(params, shardings)
    state = opt.init(params)
    f = step_fn(model, opt)
    for s in range(4):
        batch = {{k: jnp.asarray(v) for k, v in data.batch(s).items()}}
        params, state, loss = f(params, state, batch)
mgr = CheckpointManager({ck!r}, async_write=False)
mgr.save(4, {{"params": params, "opt_state": state}})
print("PHASE1", float(loss))
""", devices=4)
    assert "PHASE1" in out1

    # phase 2: pool shrinks to 2 devices (2x1); resharded restore + 2 steps
    out2 = run_sub(TRAIN_SNIPPET + f"""
cfg, model, opt, data = build()
mesh = make_mesh((2, 1), ("data", "model"))
info = MeshInfo(mesh)
mgr = CheckpointManager({ck!r}, async_write=False)
with use_mesh_info(info), mesh:
    like_p = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    axes = model.param_axes
    flatmap = {{}}
    import jax.tree_util as jtu
    for path, ax in jtu.tree_flatten_with_path(
            axes, is_leaf=lambda a: isinstance(a, tuple)
            and all(isinstance(e, (str, type(None))) for e in a))[0]:
        key = "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                       for p in path)
        flatmap["params/" + key] = ax
    def sharding_fn(key):
        ax = flatmap.get(key)
        if ax is None:  # opt moments mirror params; step is replicated
            ax = flatmap.get(key.replace("opt_state/m/", "params/")
                             .replace("opt_state/v/", "params/"))
        shape = None
        if ax is None:
            return info.sharding((), ())
        return None  # fall back to default put below
    like = {{"params": like_p, "opt_state": jax.eval_shape(opt.init, like_p)}}
    step, tree = mgr.restore_latest(like)
    params, state = tree["params"], tree["opt_state"]
    shardings = jax.tree.map(lambda v, ax: info.sharding(v.shape, ax),
                             params, axes)
    params = jax.device_put(params, shardings)
    f = step_fn(model, opt)
    losses = []
    for s in range(step, step + 2):
        batch = {{k: jnp.asarray(v) for k, v in data.batch(s).items()}}
        params, state, loss = f(params, state, batch)
        losses.append(float(loss))
print("PHASE2", losses)
""", devices=2)
    assert "PHASE2" in out2

    # reference: straight 6-step single-device run
    out3 = run_sub(TRAIN_SNIPPET + """
cfg, model, opt, data = build()
params = model.init(jax.random.PRNGKey(0))
state = opt.init(params)
f = step_fn(model, opt)
losses = []
for s in range(6):
    batch = {k: jnp.asarray(v) for k, v in data.batch(s).items()}
    params, state, loss = f(params, state, batch)
    losses.append(float(loss))
print("REF", losses[-2:])
""", devices=1)
    ref = eval(out3.split("REF", 1)[1].strip())
    got = eval(out2.split("PHASE2", 1)[1].strip())
    for a, b in zip(got, ref):
        assert abs(a - b) < 2e-3, (got, ref)
