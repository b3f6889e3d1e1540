"""End-to-end training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch phi3-mini-3.8b \
        --smoke --steps 50 --remat offload --offload-opt-state

``--smoke`` trains the reduced config on however many local devices exist;
without it the full config is used (requires real accelerators — on this
CPU host the full configs only lower via launch/dryrun.py). Checkpoints go
to --ckpt-dir every --ckpt-every steps; training resumes from the latest
checkpoint if one exists.
"""

from __future__ import annotations

import argparse
import os
import time

import jax
import jax.numpy as jnp

from repro.api import HyperOffloadSession, OffloadConfig
from repro.checkpoint.ckpt import load_checkpoint, save_checkpoint
from repro.configs import REGISTRY
from repro.data.pipeline import SyntheticTokens
from repro.launch.compile_cache import enable_compile_cache
from repro.models.model import build_model
from repro.optim.adamw import AdamWState


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=sorted(REGISTRY), required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-trainable)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--remat", choices=("none", "full", "offload"), default="none")
    ap.add_argument("--offload-opt-state", action="store_true")
    ap.add_argument("--peak-lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = REGISTRY[args.arch]
    if args.smoke:
        cfg = cfg.reduced()
    model = build_model(cfg)
    # the memory policy (remat / optimizer-state offload) lives in the
    # session config; optimization hyperparameters override per run
    session = HyperOffloadSession(OffloadConfig(
        mode="resident", remat=args.remat,
        offload_opt_state=args.offload_opt_state))
    ts = session.train_config(peak_lr=args.peak_lr,
                              warmup=max(1, args.steps // 10),
                              total_steps=args.steps)
    params, opt_state = session.init_train_state(
        model, jax.random.key(args.seed), ts=ts)
    step_fn = session.train_step(model, ts)
    data = SyntheticTokens(cfg.vocab_size, seq_len=args.seq_len,
                           global_batch=args.batch, seed=args.seed, noise=0.05)

    start = 0
    if args.ckpt_dir:
        latest = os.path.join(args.ckpt_dir, "latest.npz")
        if os.path.exists(latest):
            params, start = load_checkpoint(latest, params)
            print(f"resumed from {latest} at step {start}")

    n_params = sum(x.size for x in jax.tree.leaves(params))
    print(f"arch={cfg.name} params={n_params/1e6:.1f}M remat={args.remat} "
          f"opt_offload={args.offload_opt_state}")
    t0 = time.time()
    for i in range(start, args.steps):
        batch = data.batch(i, cfg)
        params, opt_state, metrics = step_fn(params, opt_state, batch)
        if i % args.log_every == 0 or i == args.steps - 1:
            dt = time.time() - t0
            print(f"step {i:5d}  loss {float(metrics['loss']):.4f}  "
                  f"gnorm {float(metrics['grad_norm']):.3f}  "
                  f"lr {float(metrics['lr']):.2e}  ({dt:.1f}s)")
        if args.ckpt_dir and (i + 1) % args.ckpt_every == 0:
            save_checkpoint(os.path.join(args.ckpt_dir, "latest.npz"), params, i + 1)
    final_loss = float(metrics["loss"])
    print(f"done: final loss {final_loss:.4f}")
    session.close()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
