"""Training driver wired to the Mercury services.

Single-process topology (the multi-process topology is the same code with
tcp URIs — see examples/checkpoint_restart.py and the integration tests):
  * a checkpoint server engine (restore on start, async save every
    --ckpt-every steps),
  * a datafeed engine hosting the token pipeline,
  * a membership coordinator the trainer heartbeats to,
  * the jit'd train step from repro.train.step.

Usage:
  PYTHONPATH=src python -m repro.launch.train --arch qwen1.5-0.5b \
      --reduced --steps 20
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.configs.base import ParallelConfig
from repro.core.executor import Engine
from repro.data.pipeline import SyntheticSource
from repro.launch.compile_cache import enable_compile_cache
from repro.models import Model, unzip
from repro.services import (CheckpointClient, CheckpointServer,
                            DataFeedClient, DataFeedServer,
                            MembershipClient, MembershipServer)
from repro.train import optim
from repro.train.step import make_train_step


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-0.5b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--ckpt-uri", default=None,
                    help="external checkpoint server URI (tcp://…)")
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = configs.reduced(args.arch) if args.reduced else configs.get(args.arch)
    model = Model(cfg)
    opt_cfg = optim.OptConfig(lr=args.lr, warmup=5, decay_steps=args.steps)
    par = ParallelConfig(microbatches=args.microbatches, remat="none")

    # --- services -----------------------------------------------------------
    trainer = Engine(None)                      # self plugin (in-process)
    if args.ckpt_uri:
        ckpt_server_uri = args.ckpt_uri
    else:
        ckpt_engine = Engine(None)
        CheckpointServer(ckpt_engine)
        ckpt_server_uri = ckpt_engine.uri
    ckpt = CheckpointClient(trainer, ckpt_server_uri)

    feed_engine = Engine(None)
    frontend = None
    if cfg.frontend != "none":
        frontend = (cfg.frontend_seq, cfg.frontend_dim)
    source = SyntheticSource(cfg.vocab, args.seq, args.batch,
                             frontend=frontend)
    DataFeedServer(feed_engine, source)
    feed = DataFeedClient(trainer, [feed_engine.uri], depth=2)

    coord = Engine(None)
    MembershipServer(coord)
    member = MembershipClient(trainer, coord.uri, "trainer-0")
    member.join({"role": "trainer"})

    # --- state --------------------------------------------------------------
    state, axes = __import__("repro.train.step", fromlist=["init_state"]) \
        .init_state(model, opt_cfg, jax.random.PRNGKey(0))
    start_step = 0
    if args.resume:
        try:
            state, start_step = ckpt.restore(cfg.name, state)
            print(f"resumed from step {start_step}")
        except Exception as e:
            print(f"no checkpoint to resume ({e}); starting fresh")

    step_fn = jax.jit(make_train_step(model, opt_cfg, par, mesh=None,
                                      impl="xla"))

    # --- loop ---------------------------------------------------------------
    t0 = time.monotonic()
    pending_save = None
    for step in range(start_step, start_step + args.steps):
        raw = feed.get(step)
        batch = {k: jnp.asarray(v) for k, v in raw.items()
                 if k in ("tokens", "targets", "frontend")}
        if cfg.family == "vlm":
            F = cfg.frontend_seq
            pad = np.full((batch["tokens"].shape[0], F), -1, np.int32)
            batch["targets"] = jnp.concatenate(
                [jnp.asarray(pad), batch["targets"]], axis=1)
        state, metrics = step_fn(state, batch)
        if (step + 1) % args.ckpt_every == 0 or step == start_step + args.steps - 1:
            if pending_save is not None:
                pending_save.result(timeout=120)
            host_state = jax.tree_util.tree_map(np.asarray, state)
            pending_save = ckpt.async_save(cfg.name, step + 1, host_state)
        print(f"step {step:4d} loss={float(metrics['loss']):.4f} "
              f"gnorm={float(metrics['grad_norm']):.3f} "
              f"lr={float(metrics['lr']):.2e}")
    if pending_save is not None:
        print("final checkpoint:", pending_save.result(timeout=120))
    dt = time.monotonic() - t0
    toks = args.steps * args.batch * args.seq
    print(f"{args.steps} steps, {toks} tokens, {dt:.1f}s "
          f"({toks / dt:.0f} tok/s); checkpoints: {ckpt.list()}")
    member.leave()


if __name__ == "__main__":
    main()
