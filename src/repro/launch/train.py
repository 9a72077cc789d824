"""Training driver.

Runs REAL steps (CPU: use --reduced; TPU: full configs) with the same
step builders the dry-run lowers — one source of truth.

  PYTHONPATH=src python -m repro.launch.train --arch qwen1.5-0.5b \
      --reduced --steps 50 --batch 8 --seq 128
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.registry import get_config
from repro.data.synthetic import token_batches
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_dev_mesh
from repro.launch.specs import plan_args
from repro.models.transformer import Runtime, init_model
from repro.optim.adamw import adamw_init
from repro.optim.schedules import cosine_schedule, wsd_schedule
from repro.train import checkpoint as ckpt
from repro.train.steps import make_train_step


def build_lr_fn(cfg, base_lr: float, total_steps: int):
    if cfg.lr_schedule == "wsd":
        return wsd_schedule(base_lr, warmup=max(10, total_steps // 20),
                            stable=int(total_steps * 0.7),
                            total=total_steps)
    return cosine_schedule(base_lr, warmup=max(10, total_steps // 20),
                           total=total_steps)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true",
                    help="train the smoke-scale variant (CPU-friendly)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--ckpt", default="", help="save checkpoint here at the end")
    ap.add_argument("--data-mesh", type=int, default=0,
                    help="devices for a (data, model) dev mesh (0 = single)")
    ap.add_argument("--model-mesh", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()

    mesh = None
    rt = Runtime()
    if args.data_mesh and args.model_mesh:
        mesh = make_dev_mesh(args.data_mesh, args.model_mesh)
        rt = Runtime(mesh=mesh, ep=cfg.is_moe, ep_ranks=args.model_mesh,
                     use_duplication=False)

    params = init_model(jax.random.PRNGKey(args.seed), cfg)
    n_params = sum(np.prod(p.shape) for p in jax.tree.leaves(params))
    print(f"arch={cfg.name} params={n_params/1e6:.1f}M "
          f"(analytical {cfg.num_params()/1e6:.1f}M) "
          f"family={cfg.family} moe={cfg.is_moe}")

    opt = adamw_init(params)
    lr_fn = build_lr_fn(cfg, args.lr, args.steps)
    step_fn = jax.jit(make_train_step(cfg, rt, lr_fn=lr_fn))
    plan = plan_args(cfg, rt.ep_ranks) if rt.ep else None

    gen = token_batches(args.seed, cfg.vocab_size, args.batch, args.seq)
    losses = []
    t0 = time.perf_counter()
    for step in range(args.steps):
        raw = next(gen)
        batch = {k: jnp.asarray(v) for k, v in raw.items()}
        if cfg.input_mode == "mixed" and cfg.num_prefix_embeddings:
            batch["prefix_embeds"] = jnp.zeros(
                (args.batch, cfg.num_prefix_embeddings, cfg.d_model),
                jnp.bfloat16)
        if cfg.is_encdec:
            batch["frames"] = jnp.zeros(
                (args.batch, min(64, cfg.encoder.max_source_len),
                 cfg.encoder.d_model), jnp.bfloat16)
        ctx = mesh or _null()
        with ctx:
            params, opt, metrics = step_fn(params, opt, batch, plan)
        losses.append(float(metrics["loss"]))
        if step % args.log_every == 0 or step == args.steps - 1:
            extra = ""
            if cfg.is_moe and metrics.get("expert_counts") is not None:
                c = np.asarray(metrics["expert_counts"]).sum(0)
                extra = f" skew={c.max() / max(c.mean(), 1e-9):.2f}"
            print(f"step {step:4d} loss={losses[-1]:.4f} "
                  f"lr={float(metrics['lr']):.2e} "
                  f"gnorm={float(metrics['grad_norm']):.2f}{extra}")
    dt = time.perf_counter() - t0
    print(f"done: {args.steps} steps in {dt:.1f}s "
          f"({dt / args.steps * 1e3:.0f} ms/step); "
          f"loss {losses[0]:.3f} -> {losses[-1]:.3f}")

    if args.ckpt:
        ckpt.save(args.ckpt, {"params": params, "opt": opt})
        print(f"checkpoint saved to {args.ckpt}")
    return 0 if losses[-1] < losses[0] else 1


class _null:
    def __enter__(self):
        return self
    def __exit__(self, *a):
        return False


if __name__ == "__main__":
    raise SystemExit(main())
