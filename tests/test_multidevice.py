"""Multi-device semantics on a small forced-host-device mesh, run in
subprocesses so the main test process keeps a single device (the dry-run is
the only place that forces 512).

Covers: sharded-vs-single-device numerics parity for the train loss (incl.
the shard_map MoE path), gradient-compression error feedback, and the GPipe
pipeline vs the sequential reference.
"""
import subprocess
import sys
import textwrap



def run_sub(code: str, devices: int = 8, timeout: int = 560) -> str:
    prog = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = (
            "--xla_force_host_platform_device_count={devices}")
        {textwrap.indent(textwrap.dedent(code), '        ').strip()}
    """)
    # forced host devices are CPU devices: never let a child probe libtpu
    env = {"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
           "JAX_PLATFORMS": "cpu"}
    r = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                       text=True, timeout=timeout, env=env)
    assert r.returncode == 0, f"STDOUT:{r.stdout[-2000:]}\nERR:{r.stderr[-3000:]}"
    return r.stdout


def test_sharded_loss_matches_single_device_moe():
    """deepseek-v2 smoke (MoE+MLA) on a 2x2 mesh == unsharded, exercising the
    shard_map dispatch path against the dense path."""
    out = run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs.deepseek_v2_236b import smoke
        from repro.models import LanguageModel
        from repro.models import moe as moe_mod
        from repro.distributed.sharding import MeshInfo, use_mesh_info
        from repro.launch.mesh import make_mesh

        moe_mod._SMALL_T = 16  # force the shard_map path for tiny smoke shapes
        cfg = smoke().scaled(compute_dtype="float32", n_experts=8,
                             d_model=64)
        model = LanguageModel(cfg)
        params = model.init(jax.random.PRNGKey(0))
        rng = np.random.RandomState(0)
        B, S = 4, 32
        batch = {
            "tokens": jnp.asarray(rng.randint(0, cfg.vocab_size, (B, S))),
            "targets": jnp.asarray(rng.randint(0, cfg.vocab_size, (B, S))),
            "weights": jnp.ones((B, S), jnp.float32),
        }
        ref, _ = jax.jit(model.train_loss)(params, batch)

        mesh = make_mesh((2, 2), ("data", "model"))
        info = MeshInfo(mesh)
        with use_mesh_info(info), mesh:
            axes = model.param_axes
            shardings = jax.tree.map(
                lambda v, ax: info.sharding(v.shape, ax), params, axes)
            params_s = jax.device_put(params, shardings)
            batch_s = jax.device_put(batch, {
                k: info.sharding(v.shape, ("batch", "seq_act"))
                for k, v in batch.items()})
            sharded, _ = jax.jit(model.train_loss)(params_s, batch_s)
        np.testing.assert_allclose(float(ref), float(sharded), rtol=2e-4)
        print("PARITY OK", float(ref), float(sharded))
    """)
    assert "PARITY OK" in out


def test_moe_dropped_counter_counts_capacity_overflow():
    """The shard_map path reports the (token, expert) assignments it drops:
    none when capacity covers every local token (capacity_factor =
    n_experts / top_k), some when capacity is starved; the dense path never
    drops."""
    out = run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs.granite_moe_1b_a400m import smoke
        from repro.models import LanguageModel
        from repro.models import moe as moe_mod
        from repro.distributed.sharding import MeshInfo, use_mesh_info
        from repro.launch.mesh import make_mesh

        moe_mod._SMALL_T = 16  # force the shard_map path for tiny smoke shapes
        B, S = 4, 64
        rng = np.random.RandomState(0)
        info = MeshInfo(make_mesh((2, 2), ("data", "model")))
        dropped = {}
        for cf in (1.0, 0.05):
            base = smoke()
            cfg = base.scaled(compute_dtype="float32",
                              capacity_factor=cf * base.n_experts / base.top_k)
            model = LanguageModel(cfg)
            params = model.init(jax.random.PRNGKey(0))
            batch = {
                "tokens": jnp.asarray(rng.randint(0, cfg.vocab_size, (B, S))),
                "targets": jnp.asarray(rng.randint(0, cfg.vocab_size, (B, S))),
            }
            _, dense = jax.jit(model.train_loss)(params, batch)
            assert float(dense["moe_dropped"]) == 0.0
            with use_mesh_info(info), info.mesh:
                _, m = jax.jit(model.train_loss)(params, batch)
            dropped[cf] = float(m["moe_dropped"])
        assert dropped[1.0] == 0.0 and dropped[0.05] > 0, dropped
        print("DROPS OK", dropped)
    """, devices=4)
    assert "DROPS OK" in out


def test_sharded_loss_matches_single_device_gqa():
    """qwen smoke (GQA + expanded-KV path) sharded == unsharded."""
    out = run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs.qwen2_vl_72b import smoke
        from repro.models import LanguageModel
        from repro.distributed.sharding import MeshInfo, use_mesh_info
        from repro.launch.mesh import make_mesh

        cfg = smoke().scaled(compute_dtype="float32")
        model = LanguageModel(cfg)
        params = model.init(jax.random.PRNGKey(0))
        rng = np.random.RandomState(1)
        B, S = 4, 64
        batch = {
            "tokens": jnp.asarray(rng.randint(0, cfg.vocab_size, (B, S))),
            "targets": jnp.asarray(rng.randint(0, cfg.vocab_size, (B, S))),
            "weights": jnp.ones((B, S), jnp.float32),
        }
        ref, _ = jax.jit(model.train_loss)(params, batch)
        mesh = make_mesh((2, 2), ("data", "model"))
        info = MeshInfo(mesh)
        with use_mesh_info(info), mesh:
            axes = model.param_axes
            shardings = jax.tree.map(
                lambda v, ax: info.sharding(v.shape, ax), params, axes)
            params_s = jax.device_put(params, shardings)
            sharded, _ = jax.jit(model.train_loss)(params_s, batch)
        np.testing.assert_allclose(float(ref), float(sharded), rtol=2e-4)
        print("PARITY OK")
    """)
    assert "PARITY OK" in out


def test_grad_compression_error_feedback():
    out = run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.distributed.collectives import compressed_psum
        from repro.launch.mesh import make_mesh

        mesh = make_mesh((4,), ("pod",))
        g_global = jax.random.normal(jax.random.PRNGKey(0), (4, 64))

        def f(g, e):
            m, ne = compressed_psum(g[0], "pod", e[0])
            return m[None], ne[None]

        e = jnp.zeros((4, 64))
        sm = jax.shard_map(f, mesh=mesh, in_specs=(P("pod"), P("pod")),
                           out_specs=(P("pod"), P("pod")), check_vma=False)
        true_mean = jnp.mean(g_global, axis=0)
        # single round: bounded quantization error
        m, e1 = sm(g_global, e)
        err1 = float(jnp.max(jnp.abs(m[0] - true_mean)))
        scale = float(jnp.max(jnp.abs(g_global)) / 127.0)
        assert err1 <= scale + 1e-6, (err1, scale)
        # error feedback: summed estimates over repeated rounds of the SAME
        # gradient converge to the true mean (residual carrying)
        est_sum = jnp.zeros(64)
        e = jnp.zeros((4, 64))
        for _ in range(20):
            m, e = sm(g_global, e)
            est_sum = est_sum + m[0]
        avg = est_sum / 20
        np.testing.assert_allclose(np.asarray(avg), np.asarray(true_mean),
                                   atol=5e-3)
        print("COMPRESS OK", err1)
    """, devices=4)
    assert "COMPRESS OK" in out


def test_pipeline_matches_sequential():
    out = run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.distributed.pipeline import pipeline_apply
        from repro.launch.mesh import make_mesh

        n_stages, n_micro, mb, d = 4, 6, 2, 8
        mesh = make_mesh((n_stages,), ("model",))
        ks = jax.random.split(jax.random.PRNGKey(0), n_stages)
        params = {"w": jnp.stack([jax.random.normal(k, (d, d)) * 0.3
                                  for k in ks]),
                  "b": jnp.stack([jnp.ones((d,)) * 0.01] * n_stages)}
        x = jax.random.normal(jax.random.PRNGKey(1), (n_micro, mb, d))

        def stage_fn(p, h):
            return jnp.tanh(h @ p["w"] + p["b"])

        out = pipeline_apply(stage_fn, params, x, mesh, axis="model")
        ref = x
        for i in range(n_stages):
            p_i = jax.tree.map(lambda a: a[i], params)
            ref = jax.vmap(lambda m: stage_fn(p_i, m))(ref)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)
        print("PIPELINE OK")
    """, devices=4)
    assert "PIPELINE OK" in out


def test_small_mesh_dryrun_cell():
    """lower+compile a reduced arch on a 2x2 mesh end-to-end (the dry-run
    machinery itself, CI-scale)."""
    out = run_sub("""
        import jax, jax.numpy as jnp
        from repro.configs.granite_moe_1b_a400m import smoke
        from repro.distributed.sharding import MeshInfo, use_mesh_info
        from repro.launch.specs import param_specs, batch_specs
        from repro.launch.dryrun import make_train_step, _opt_specs, shardings_of
        from repro.models import LanguageModel
        from repro.optim import AdamW, OptConfig
        from repro.configs.base import ShapeSpec
        from repro.launch.mesh import make_mesh

        cfg = smoke()
        shape = ShapeSpec("t", "train", 64, 4)
        mesh = make_mesh((2, 2), ("data", "model"))
        info = MeshInfo(mesh)
        model = LanguageModel(cfg)
        opt = AdamW(OptConfig())
        with use_mesh_info(info), mesh:
            psds = param_specs(model, info)
            osds = _opt_specs(model, opt, info, psds)
            bsds = batch_specs(cfg, shape, info)
            fn = jax.jit(make_train_step(model, opt, shardings_of(psds)),
                         donate_argnums=(0, 1))
            compiled = fn.lower(psds, osds, bsds).compile()
        ca = compiled.cost_analysis()
        print("COMPILED OK", ca.get("flops", 0) > 0)
    """, devices=4)
    assert "COMPILED OK" in out
