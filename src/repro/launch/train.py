"""Distributed training launcher.

``python -m repro.launch.train --arch gemma-2b --smoke --steps 20``

On real hardware the same entry point drives the production mesh
(``--mesh pod`` / ``--mesh multipod``); on this CPU container use
``--smoke`` (reduced config, 1-device mesh) — same code path, same
sharding rules, degenerate mesh.  Supports Heroes composition as a
first-class switch (``--composition``) and checkpoint/resume.
"""

from __future__ import annotations

import argparse
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import configs
from repro.compile_cache import enable_compile_cache
from repro.checkpoint import restore_latest, save_checkpoint
from repro.configs.base import CompositionConfig
from repro.data import SyntheticTextTask, lm_batches
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.launch.steps import make_train_step
from repro.models import model
from repro.models.module import count_params
from repro.optim import cosine_schedule, make_optimizer
from repro.sharding import rules
from repro.sharding.context import set_context


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma-2b", choices=configs.list_archs())
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config on the host mesh (CPU)")
    ap.add_argument("--mesh", default="host", choices=["host", "pod", "multipod"])
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--optimizer", default="adamw")
    ap.add_argument("--composition", action="store_true",
                    help="train the Heroes-factorized parameterisation")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = configs.get_smoke(args.arch) if args.smoke else configs.get_config(args.arch)
    if args.composition:
        cfg = cfg.replace(composition=CompositionConfig(
            enabled=True, max_width=2, rank=cfg.d_model // 4))
    if cfg.family in ("vlm", "audio"):
        print(f"note: {args.arch} uses stub frontends; training on synthetic "
              "token streams with stub embeddings")

    mesh = {"host": make_host_mesh,
            "pod": lambda: make_production_mesh(multi_pod=False),
            "multipod": lambda: make_production_mesh(multi_pod=True)}[args.mesh]()
    dp = rules.dp_axes_for(mesh)
    set_context(mesh, dp)

    key = jax.random.PRNGKey(0)
    params = model.init(key, cfg)
    print(f"{cfg.arch_id}: {count_params(params):,} params "
          f"(composition={'on' if args.composition else 'off'}), "
          f"mesh={mesh.shape}")

    opt = make_optimizer(args.optimizer, cosine_schedule(args.lr, args.steps, 5))
    opt_state = opt.init(params)

    start = 0
    if args.ckpt_dir:
        restored = restore_latest(args.ckpt_dir)
        if restored:
            start, state = restored
            params, opt_state = state["params"], state["opt"]
            print(f"resumed from step {start}")

    pspecs = rules.param_specs(jax.eval_shape(lambda: params), mesh=mesh)
    shard = lambda tree, specs: jax.tree_util.tree_map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), tree, specs)
    params = shard(params, pspecs)

    step_fn = jax.jit(make_train_step(cfg, opt))
    task = SyntheticTextTask(vocab=min(cfg.vocab, 512), seq_len=args.seq)
    rng = np.random.default_rng(0)

    t0 = time.time()
    for i in range(start, args.steps):
        toks, labels = lm_batches(task.train, args.batch, rng)
        batch = {"tokens": jnp.asarray(toks % cfg.vocab),
                 "labels": jnp.asarray(labels % cfg.vocab)}
        if cfg.family == "vlm":
            emb = model._input_embeddings(params, cfg, batch)
            pos = jnp.broadcast_to(
                jnp.arange(args.seq, dtype=jnp.int32)[None, None, :],
                (args.batch, 3, args.seq))
            batch = {"embeddings": emb, "positions": pos, "labels": batch["labels"]}
        if cfg.family == "audio":
            se = min(cfg.encdec.encoder_seq, 64)
            batch["enc_embeddings"] = 0.02 * jax.random.normal(
                jax.random.PRNGKey(i), (args.batch, se, cfg.d_model))
            batch["enc_mask"] = jnp.ones((args.batch, se), bool)
        params, opt_state, metrics = step_fn(params, opt_state, batch)
        if i % 10 == 0 or i == args.steps - 1:
            print(f"step {i:4d}  loss {float(metrics['loss']):.4f}  "
                  f"grad_norm {float(metrics['grad_norm']):.3f}  "
                  f"{(time.time()-t0):.1f}s")
        if args.ckpt_dir and args.ckpt_every and (i + 1) % args.ckpt_every == 0:
            save_checkpoint(args.ckpt_dir, i + 1,
                            {"params": params, "opt": opt_state})
    print("done.")


if __name__ == "__main__":
    main()
