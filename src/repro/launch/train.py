"""End-to-end training driver: data pipeline -> sharded train step ->
checkpoint/restart fault tolerance.

On this CPU container it runs reduced (--smoke) configs for real; on a pod it
is the same code with --mesh pod/multipod (the dry-run proves those lower).
Auto-resume: the latest committed checkpoint is picked up after any crash or
preemption (exercised by tests/test_train_resume.py with --preempt-at).
"""
from __future__ import annotations

import argparse
import importlib
import time
from typing import Any

import jax
import jax.numpy as jnp

from repro.checkpoint import CheckpointManager
from repro.configs import get_config
from repro.data import TokenDataset
from repro.distributed.sharding import MeshInfo, use_mesh_info
from repro.models import LanguageModel
from repro.optim import AdamW, OptConfig
from repro.utils import enable_compile_cache


def smoke_config(arch: str):
    mod = importlib.import_module(
        "repro.configs." + arch.replace("-", "_").replace(".", "_"))
    return mod.smoke()


def make_train_step(model: LanguageModel, opt: AdamW):
    def train_step(params, opt_state, batch):
        (_, metrics), grads = jax.value_and_grad(
            model.train_loss, has_aux=True)(params, batch)
        new_params, new_state, stats = opt.update(grads, opt_state, params)
        return new_params, new_state, {**metrics, **stats}

    return jax.jit(train_step, donate_argnums=(0, 1))


def train(arch: str = "gemma-2b", smoke: bool = True, steps: int = 50,
          global_batch: int = 8, seq_len: int = 128, peak_lr: float = 3e-3,
          ckpt_dir: str | None = None, save_every: int = 20,
          log_every: int = 10, resume: bool = True, seed: int = 0,
          preempt_at: int | None = None, mesh_info: MeshInfo | None = None,
          partition: str = "2024-01/all") -> dict[str, Any]:
    cfg = smoke_config(arch) if smoke else get_config(arch)
    model = LanguageModel(cfg)
    opt = AdamW(OptConfig(peak_lr=peak_lr, warmup_steps=max(2, steps // 10),
                          decay_steps=max(steps, 10)))
    data = TokenDataset(vocab_size=cfg.vocab_size, seq_len=seq_len,
                        global_batch=global_batch, partition=partition)

    with use_mesh_info(mesh_info):
        params = jax.jit(model.init)(jax.random.PRNGKey(seed))
        opt_state = opt.init(params)
        step = 0

        mgr = None
        if ckpt_dir:
            mgr = CheckpointManager(ckpt_dir, keep=3)
            if resume:
                got = mgr.restore_latest({"params": params,
                                          "opt_state": opt_state})
                if got is not None:
                    step, tree = got
                    params, opt_state = tree["params"], tree["opt_state"]
                    print(f"[train] resumed from step {step}")

        train_step = make_train_step(model, opt)
        history: list[dict[str, float]] = []
        t0 = time.time()
        while step < steps:
            batch = {k: jnp.asarray(v) for k, v in data.batch(step).items()}
            params, opt_state, metrics = train_step(params, opt_state, batch)
            step += 1
            if step % log_every == 0 or step == steps:
                m = {k: float(v) for k, v in metrics.items()}
                m["step"] = step
                m["wall_s"] = time.time() - t0
                history.append(m)
                print(f"[train {arch}] step {step}: loss={m['loss']:.4f} "
                      f"gnorm={m['grad_norm']:.3f} lr={m['lr']:.2e}")
            if mgr and (step % save_every == 0 or step == steps):
                mgr.save(step, {"params": params, "opt_state": opt_state},
                         metadata={"arch": arch, "step": step})
            if preempt_at is not None and step >= preempt_at:
                mgr and mgr.wait()
                print(f"[train] simulated preemption at step {step}")
                raise SystemExit(17)  # preemption exit code
        if mgr:
            mgr.wait()

    losses = [h["loss"] for h in history]
    return {"history": history, "final_loss": losses[-1] if losses else None,
            "first_loss": losses[0] if losses else None, "steps": step,
            "params": params}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="gemma-2b")
    ap.add_argument("--full", action="store_true",
                    help="use the full (pod-scale) config, not the smoke one")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--save-every", type=int, default=20)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--no-resume", action="store_true")
    ap.add_argument("--preempt-at", type=int, default=None)
    ap.add_argument("--partition", default="2024-01/all")
    args = ap.parse_args()
    enable_compile_cache()
    out = train(arch=args.arch, smoke=not args.full, steps=args.steps,
                global_batch=args.batch, seq_len=args.seq, peak_lr=args.lr,
                ckpt_dir=args.ckpt_dir, save_every=args.save_every,
                log_every=args.log_every, resume=not args.no_resume,
                preempt_at=args.preempt_at, partition=args.partition)
    print(f"[train] done: first_loss={out['first_loss']:.4f} "
          f"final_loss={out['final_loss']:.4f}")


if __name__ == "__main__":
    main()
