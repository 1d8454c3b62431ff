"""End-to-end training driver (reduced scale on CPU, production on TPU).

Wires together: config -> model init -> sharded train step -> synthetic
data -> checkpoint manager -> fault-tolerance supervisor.

Usage:
  PYTHONPATH=src python -m repro.launch.train --arch musicgen-large \
      --steps 50 --batch 8 --seq 64 --reduced
  ... --peft clover      # CLOVER-S fine-tuning instead of full training
  ... --clover-prune 0.5 # prune first, then train (recovery setting)
"""
from __future__ import annotations

import argparse
import sys
import time
from typing import Any, Dict, Optional

import jax

from repro.configs import get_config
from repro.core import clover_decompose, clover_prune
from repro.data import SyntheticConfig, SyntheticLM, make_global_batch
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.models import transformer as T
from repro.optim import AdamWConfig
from repro.parallel import sharding as sh
from repro.train.checkpoint import CheckpointManager
from repro.train.step import (TrainConfig, make_opt_state,
                              make_train_step)
from repro.train.supervisor import Supervisor, WorkerFailure


def build(arch: str, *, reduced: bool, batch: int, seq: int,
          steps: int, peft: Optional[str], prune_ratio: float,
          lr: float, seed: int = 0):
    cfg = get_config(arch)
    if reduced:
        cfg = cfg.reduced()
    key = jax.random.PRNGKey(seed)
    params = T.init_lm_params(cfg, key)

    if prune_ratio > 0:
        params, cfg, _ = clover_decompose(params, cfg,
                                          peft=(peft == "clover"))
        params, cfg = clover_prune(params, cfg, qk_ratio=prune_ratio,
                                   vo_ratio=prune_ratio)
    elif peft == "clover":
        params, cfg, _ = clover_decompose(params, cfg, peft=True)

    mesh = make_host_mesh()
    tcfg = TrainConfig(
        optimizer=AdamWConfig(lr=lr, weight_decay=0.0 if peft else 0.1),
        warmup_steps=max(2, steps // 20),
        total_steps=steps,
        remat=True,
        peft_mode=(peft == "clover"))
    step_fn, _ = make_train_step(cfg, tcfg, mesh)
    opt_state = make_opt_state(params, peft_mode=tcfg.peft_mode)

    data = SyntheticLM(SyntheticConfig(
        vocab_size=cfg.vocab_size, seq_len=seq, global_batch=batch,
        seed=seed))
    jitted = jax.jit(step_fn, donate_argnums=(0, 1))
    return cfg, mesh, params, opt_state, data, jitted


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="musicgen-large")
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--peft", choices=["clover"], default=None)
    ap.add_argument("--clover-prune", type=float, default=0.0)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--fail-at", type=int, default=-1,
                    help="inject a WorkerFailure at this step (FT demo)")
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg, mesh, params, opt_state, data, jitted = build(
        args.arch, reduced=args.reduced, batch=args.batch, seq=args.seq,
        steps=args.steps, peft=args.peft, prune_ratio=args.clover_prune,
        lr=args.lr)

    ckpt = CheckpointManager(args.ckpt_dir, keep=2)
    sup = Supervisor(ckpt, ckpt_every=args.ckpt_every)
    state: Dict[str, Any] = {"params": params, "opt": opt_state,
                             "data": data}
    spec = sh.data_specs(mesh)
    failed_once = {"done": False}

    def step_fn(st, i):
        if i == args.fail_at and not failed_once["done"]:
            failed_once["done"] = True
            raise WorkerFailure(f"injected failure at step {i}")
        batch_np = st["data"].batch_at(i)
        batch = make_global_batch(batch_np, mesh, spec)
        with sh.use_mesh(mesh):
            p, o, metrics = jitted(st["params"], st["opt"], batch)
        st = {"params": p, "opt": o, "data": st["data"]}
        st["data"].step = i + 1
        return st, metrics

    def save_tree(st):
        return ({"params": st["params"], "opt": st["opt"]},
                {"data": st["data"].state_dict()})

    def restore_tree(tree, extra):
        data.load_state_dict(extra["data"])
        return {"params": tree["params"], "opt": tree["opt"],
                "data": data}

    def metrics_cb(i, m):
        if i % 5 == 0 or i == args.steps - 1:
            print(f"step {i:5d}  loss {float(m['loss']):.4f}  "
                  f"gnorm {float(m['grad_norm']):.3f}")

    t0 = time.time()
    rep = sup.run(state=state, step_fn=step_fn, save_tree=save_tree,
                  restore_tree=restore_tree, start_step=0,
                  total_steps=args.steps, metrics_cb=metrics_cb)
    dt = time.time() - t0
    print(f"done: {rep.steps_run} steps ({rep.restarts} restarts, "
          f"{len(rep.stragglers)} stragglers flagged) in {dt:.1f}s; "
          f"final loss {rep.metrics_history[-1]['loss']:.4f}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
