"""Distributed training driver.

Jits the same ``train_step`` the dry-run lowers, with the same sharding
plan, against whatever devices are actually available:

  * on a real TPU slice this is the production launcher
    (``--mesh data,model`` sizes must multiply to the device count);
  * on a CPU it runs the REDUCED config end-to-end (the ``--smoke``
    path used by examples and CI).

Usage:
    PYTHONPATH=src python -m repro.launch.train --arch yi-6b --smoke \
        --steps 50 --batch 8 --seq 128
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.launch import sharding as sh
from repro.launch.compile_cache import enable_compile_cache
from repro.models import transformer as T
from repro.models.frontend import frontend_embeddings
from repro.train.checkpoint import save_checkpoint
from repro.train.loop import make_train_step
from repro.train.optimizer import AdamWConfig, init_opt_state


def make_batches(cfg, batch: int, seq: int, seed: int = 0):
    """Synthetic LM / classification batch stream for the smoke path."""
    rng = np.random.default_rng(seed)
    while True:
        if cfg.family == "vlm":
            half = seq // 2
            yield {"embeds": frontend_embeddings(cfg, batch, half, seed),
                   "tokens": jnp.asarray(
                       rng.integers(1, cfg.vocab_size, (batch, half)),
                       jnp.int32)}
        elif cfg.takes_embeddings:
            b = {"embeds": frontend_embeddings(cfg, batch, seq, seed)}
            if cfg.is_encoder:
                b["labels"] = jnp.asarray(
                    rng.integers(0, cfg.num_classes, (batch, seq)),
                    jnp.int32)
            yield b
        else:
            yield {"tokens": jnp.asarray(
                rng.integers(1, cfg.vocab_size, (batch, seq)), jnp.int32)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--mesh", default="",
                    help="'data,model' sizes, e.g. '16,16' (default: all "
                         "devices on 'data')")
    ap.add_argument("--checkpoint", default="")
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.reduced()
    enable_compile_cache()
    ndev = len(jax.devices())
    if args.mesh:
        shape = tuple(int(x) for x in args.mesh.split(","))
    else:
        shape = (ndev, 1)
    mesh = jax.make_mesh(shape, ("data", "model"),
                         (jax.sharding.AxisType.Auto,) * 2)
    print(f"[train] {cfg.name}: mesh {dict(zip(mesh.axis_names, shape))} "
          f"on {ndev} device(s)")

    opt_cfg = AdamWConfig(lr=args.lr, warmup_steps=max(args.steps // 10, 1),
                          total_steps=args.steps)
    step_fn = make_train_step(cfg, opt_cfg, remat=True)

    pshard = sh.params_shardings(cfg, mesh, fsdp=ndev > 8)
    oshard = sh.opt_shardings(cfg, mesh, pshard)
    with jax.set_mesh(mesh):
        params = jax.jit(
            lambda k: T.init_params(cfg, k),
            out_shardings=pshard)(jax.random.PRNGKey(0))
    opt_state = jax.jit(init_opt_state, out_shardings=oshard)(params)

    jstep = jax.jit(step_fn, in_shardings=(pshard, oshard, None),
                    out_shardings=(pshard, oshard, None),
                    donate_argnums=(0, 1))

    batches = make_batches(cfg, args.batch, args.seq)
    t0 = time.perf_counter()
    for i in range(args.steps):
        params, opt_state, metrics = jstep(params, opt_state, next(batches))
        if (i + 1) % args.log_every == 0 or i == 0:
            m = {k: float(v) for k, v in metrics.items()}
            dt = time.perf_counter() - t0
            print(f"[train] step {i + 1:5d} loss={m['loss']:.4f} "
                  f"ce={m['ce']:.4f} acc={m['acc']:.3f} "
                  f"gnorm={m['grad_norm']:.2f} lr={m['lr']:.2e} "
                  f"({dt / (i + 1):.2f}s/step)")
    if args.checkpoint:
        save_checkpoint(args.checkpoint, params, step=args.steps)
        print(f"[train] saved {args.checkpoint}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
