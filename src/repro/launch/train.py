"""Production training launcher.

Builds the mesh from flags (or the production config), constructs the
model for ``--arch``, and drives the fault-tolerant Trainer with async
checkpoints. On a real TPU pod each host runs this same script under
``jax.distributed``. ``--smoke`` selects the reduced config (CPU-runnable);
without it the recipe trains at its published widths, on any device
count.

  PYTHONPATH=src python -m repro.launch.train --arch dlrm-criteo \
      --steps 200 --batch 1024 --ckpt-dir /tmp/ckpt
  PYTHONPATH=src python -m repro.launch.train --arch olmo-1b --smoke \
      --steps 20
"""
from __future__ import annotations

import argparse

import jax
import numpy as np

from repro.configs.registry import (
    LM_ARCHS, RECSYS_RECIPES, reduce_for_smoke,
)
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_production_mesh, make_test_mesh


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True,
                    choices=sorted(LM_ARCHS) + sorted(RECSYS_RECIPES))
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-interval", type=int, default=50)
    ap.add_argument("--mode", default="gspmd", choices=["gspmd", "manual"])
    ap.add_argument("--comm", default="auto",
                    choices=["auto", "allgather_rs", "all_to_all"],
                    help="embedding collective recipe: 'auto' picks "
                         "all_to_all for one-hot models with large "
                         "tables and allgather_rs otherwise")
    ap.add_argument("--grad-ar-dtype", default="f32",
                    choices=["f32", "bf16"],
                    help="bf16 = compressed gradient all-reduce")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-runnable)")
    ap.add_argument("--mesh", default="auto",
                    help="'auto' | 'single' | 'multi' | 'RxC'")
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args()
    enable_compile_cache()

    n_dev = len(jax.devices())
    if args.mesh == "auto":
        mesh = make_test_mesh((n_dev, 1)) if n_dev < 256 else \
            make_production_mesh()
    elif args.mesh in ("single", "multi"):
        mesh = make_production_mesh(multi_pod=args.mesh == "multi")
    else:
        r, c = (int(x) for x in args.mesh.split("x"))
        mesh = make_test_mesh((r, c))
    print(f"mesh: {dict(mesh.shape)} over {n_dev} devices")

    if args.arch in RECSYS_RECIPES:
        # recsys models go through the graph API front door: the recipe
        # module declares the layer graph, compile() lowers it — novel
        # graphs (twotower/crossdeep) run through the generic compiled
        # program, the paper recipes through their canonical configs
        import importlib

        from repro.api import Solver

        recipe = importlib.import_module(RECSYS_RECIPES[args.arch])
        solver = Solver(batch_size=args.batch, lr=args.lr,
                        grad_allreduce_dtype=args.grad_ar_dtype,
                        mode=args.mode, comm=args.comm,
                        ckpt_interval=args.ckpt_interval)
        model = recipe.build_model(smoke=args.smoke,
                                   solver=solver, mesh=mesh)
        model.compile()
        model.summary()
        hist = model.fit(steps=args.steps, ckpt_dir=args.ckpt_dir,
                         log_every=args.log_every)
        losses = [h["loss"] for h in hist]
        print(f"done: loss {losses[0]:.4f} -> {losses[-1]:.4f}, "
              f"{model.stragglers} stragglers flagged")
        return

    # LM path
    import jax.numpy as jnp
    from repro.models.lm.backbone import LMModel

    cfg = LM_ARCHS[args.arch]
    if args.smoke:
        cfg = reduce_for_smoke(cfg)
    with mesh:
        model = LMModel(cfg, mesh,
                        q_chunk=min(args.seq, 128),
                        k_chunk=min(args.seq, 128),
                        loss_chunk=min(args.seq, 128))
        params = model.init(jax.random.PRNGKey(0))
        print(f"arch {cfg.name}: embed_mode={model.embed_mode} "
              f"attn_partition={model.attn_partition}")

        @jax.jit
        def step(params, tokens):
            loss, g = jax.value_and_grad(model.train_loss)(
                params, {"tokens": tokens})
            new = jax.tree.map(
                lambda p, gg: p - args.lr * gg.astype(p.dtype), params, g)
            return new, loss

        rng = np.random.default_rng(0)
        for i in range(args.steps):
            tokens = jnp.asarray(rng.integers(
                0, cfg.vocab_size, (args.batch, args.seq)))
            params, loss = step(params, tokens)
            if i % args.log_every == 0:
                print(f"step {i:4d} loss={float(loss):.4f}")
        print(f"done: final loss {float(loss):.4f} "
              f"(ln V = {np.log(cfg.vocab_size):.2f})")


if __name__ == "__main__":
    main()
