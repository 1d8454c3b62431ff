import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512")
# ^ MUST precede any jax-importing import: jax locks the device count on
#   first backend init.  This file is the ONLY place the 512 placeholder
#   devices exist; tests and benches see the single real CPU device.

"""Multi-pod dry-run: lower + compile every (arch x shape) cell on the
production meshes and report memory / cost / collective analysis.

Usage:
  python -m repro.launch.dryrun --arch phi3-medium-14b --shape train_4k
  python -m repro.launch.dryrun --all                  # 40-cell sweep
  python -m repro.launch.dryrun --all --multi-pod      # (2,16,16) pass
  python -m repro.launch.dryrun --all --json out.json  # for benchmarks

The compile (no execution, no allocation beyond placeholder metadata)
proves the sharding config is coherent: any sharding mismatch,
compile-time OOM, or unsupported collective fails the cell.
"""
import argparse
import dataclasses
import json
import sys
import time
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs import ASSIGNED_ARCHS, SHAPES, cell_applicable, get_config
from repro.configs.base import ArchConfig
from repro.configs.shapes import ShapeConfig
from repro.launch.mesh import make_production_mesh
from repro.models import transformer as T
from repro.optim import AdamWConfig
from repro.parallel import hlo as H
from repro.parallel import sharding as sh
from repro.train.step import TrainConfig, make_train_step, make_opt_state


# The production meshes model TPU v5e pods; their roofline terms use
# that chip's published peaks (the compile itself runs on placeholder
# CPU devices, which have none).
TARGET_CHIP = H.peaks("TPU v5 lite")


def production_cfg(name: str) -> ArchConfig:
    """Full assigned config at production numerics: bf16 params/compute,
    vocab padded to 128 so the logits shard on the model axis."""
    return dataclasses.replace(get_config(name),
                               param_dtype="bfloat16",
                               compute_dtype="bfloat16",
                               pad_vocab_to=128)


def input_specs(cfg: ArchConfig, shape: ShapeConfig) -> Dict[str, Any]:
    """ShapeDtypeStruct stand-ins for every model input of this cell.

    train:   {tokens, labels} (+frontend_embeds)
    prefill: {tokens} (+frontend_embeds) — full prompt
    decode:  {token} — one new token against a seq_len KV cache
    """
    B, S = shape.global_batch, shape.seq_len
    F = cfg.frontend_len if cfg.frontend != "none" else 0
    n_tok = S - F  # backbone sees exactly seq_len positions
    i32 = jnp.int32
    cdt = jnp.bfloat16 if cfg.compute_dtype == "bfloat16" else jnp.float32
    out: Dict[str, Any] = {}
    if shape.kind == "train":
        out["tokens"] = jax.ShapeDtypeStruct((B, n_tok), i32)
        out["labels"] = jax.ShapeDtypeStruct((B, n_tok), i32)
        if F:
            out["frontend_embeds"] = jax.ShapeDtypeStruct(
                (B, F, cfg.d_model), cdt)
    elif shape.kind == "prefill":
        out["tokens"] = jax.ShapeDtypeStruct((B, n_tok), i32)
        if F:
            out["frontend_embeds"] = jax.ShapeDtypeStruct(
                (B, F, cfg.d_model), cdt)
    else:  # decode
        out["token"] = jax.ShapeDtypeStruct((B,), i32)
    return out


def _eval_shape_params(cfg: ArchConfig):
    return jax.eval_shape(
        lambda k: T.init_lm_params(cfg, k), jax.random.PRNGKey(0))


def _eval_shape_state(cfg: ArchConfig, batch: int, max_len: int):
    return jax.eval_shape(
        lambda: T.init_decode_state(cfg, batch, max_len))


# Default microbatch count for train cells: global batch 256 -> 16 per
# device on the data axis -> microbatch 2/device.  Keeps 14B-52B train
# steps inside 16GB/chip (see EXPERIMENTS.md §Dry-run).
TRAIN_MICROBATCHES = 8

# Per-arch memory tuning for the train shape (EXPERIMENTS.md §Dry-run):
# deepest model also groups remat so layer carries shrink 2x.
TRAIN_TUNING = {
    "deepseek-coder-33b": {"microbatches": 16, "remat_group": 2},
}


def lower_cell(cfg: ArchConfig, shape: ShapeConfig, mesh,
               rules: Optional[sh.ShardingRules] = None, *,
               remat: bool = True,
               microbatches: int = TRAIN_MICROBATCHES):
    """Lower the cell's step on ``mesh``; returns the jax Lowered."""
    rules = rules or sh.ShardingRules()
    ins = input_specs(cfg, shape)
    params_s = _eval_shape_params(cfg)
    pspec = sh.param_specs(params_s, mesh, rules)
    p_sh = sh.shardings(pspec, mesh)
    bspec = sh.data_specs(mesh, rules, global_batch=shape.global_batch)
    baxes = bspec[0]   # batch mesh axes, or None if batch doesn't divide

    from jax.sharding import NamedSharding, PartitionSpec as P
    tok_sh = NamedSharding(mesh, bspec)
    fe_sh = NamedSharding(mesh, P(baxes, None, None))

    if shape.kind == "train":
        tcfg = TrainConfig(optimizer=AdamWConfig(), remat=remat,
                           microbatches=microbatches)
        step, _ = make_train_step(cfg, tcfg, mesh, rules)
        opt_s = jax.eval_shape(lambda p: make_opt_state(p), params_s)
        ospec = {"m": pspec, "v": pspec, "step": P()}
        o_sh = sh.shardings(ospec, mesh)
        batch_sh = {"tokens": tok_sh, "labels": tok_sh}
        batch_shapes = {"tokens": ins["tokens"], "labels": ins["labels"]}
        if "frontend_embeds" in ins:
            batch_sh["frontend_embeds"] = fe_sh
            batch_shapes["frontend_embeds"] = ins["frontend_embeds"]
        jitted = jax.jit(step,
                         in_shardings=(p_sh, o_sh, batch_sh),
                         out_shardings=(p_sh, o_sh, None),
                         donate_argnums=(0, 1))
        with sh.use_mesh(mesh):
            return jitted.lower(params_s, opt_s, batch_shapes)

    if shape.kind == "prefill":
        state_s = _eval_shape_state(cfg, shape.global_batch, shape.seq_len)
        sspec = sh.decode_state_specs(state_s, mesh, rules)
        s_sh = sh.shardings(sspec, mesh)
        logits_sh = NamedSharding(mesh, P(baxes, None))

        def prefill_fn(params, tokens, state, fe=None):
            return T.prefill(params, cfg, tokens, state, frontend_embeds=fe)

        args = [params_s, ins["tokens"], state_s]
        in_sh = [p_sh, tok_sh, s_sh]
        if "frontend_embeds" in ins:
            args.append(ins["frontend_embeds"])
            in_sh.append(fe_sh)
        jitted = jax.jit(prefill_fn,
                         in_shardings=tuple(in_sh),
                         out_shardings=(logits_sh, s_sh),
                         donate_argnums=(2,))
        with sh.use_mesh(mesh):
            return jitted.lower(*args)

    # decode: one token against a filled cache of seq_len
    state_s = _eval_shape_state(cfg, shape.global_batch, shape.seq_len)
    sspec = sh.decode_state_specs(state_s, mesh, rules)
    s_sh = sh.shardings(sspec, mesh)
    from jax.sharding import NamedSharding, PartitionSpec as P
    tok1_sh = NamedSharding(mesh, P(baxes))
    logits_sh = NamedSharding(mesh, P(baxes, None))

    def decode_fn(params, token, state):
        return T.decode_step(params, cfg, token, state)

    jitted = jax.jit(decode_fn,
                     in_shardings=(p_sh, tok1_sh, s_sh),
                     out_shardings=(logits_sh, s_sh),
                     donate_argnums=(2,))
    with sh.use_mesh(mesh):
        return jitted.lower(params_s, ins["token"], state_s)


def calibrated_costs(cfg: ArchConfig, shape: ShapeConfig, mesh,
                     rules: Optional[sh.ShardingRules] = None, *,
                     remat: bool = True) -> Dict[str, float]:
    """Exact cost terms despite ``lax.scan``: XLA's cost_analysis counts a
    while body ONCE, so a stacked-layer model under-reports flops/bytes/
    collectives by ~n_blocks.  Unrolling is exact but compiles for many
    minutes per cell on one CPU core.  Instead, compile the SAME model at
    n_layers = period and 2*period (trip counts 1 and 2 — compiles in
    seconds) and finite-difference:

        per_block = cost(2p) - cost(p);  fixed = cost(p) - per_block
        total     = fixed + n_blocks * per_block

    This captures per-layer collectives, remat recompute, everything —
    because both compiles go through the identical partitioner."""
    out = {}
    costs = []
    for mult in (1, 2):
        small = dataclasses.replace(cfg, n_layers=cfg.period * mult,
                                    remat_group=1, unroll_layers=True)
        lowered = lower_cell(small, shape, mesh, rules, remat=remat,
                             microbatches=1)
        compiled = lowered.compile()
        rl = H.roofline_from_compiled(compiled, TARGET_CHIP)
        costs.append((rl.flops, rl.hbm_bytes, rl.coll_bytes,
                      dict(rl.coll_detail)))
    n = cfg.n_blocks
    for i, name in enumerate(("flops", "hbm_bytes", "coll_bytes")):
        per_block = costs[1][i] - costs[0][i]
        fixed = costs[0][i] - per_block
        out[name] = max(0.0, fixed + n * per_block)
    detail = {}
    for k in set(costs[0][3]) | set(costs[1][3]):
        pb = costs[1][3].get(k, 0) - costs[0][3].get(k, 0)
        fx = costs[0][3].get(k, 0) - pb
        v = max(0, fx + n * pb)
        if v:
            detail[k] = v
    out["coll_detail"] = detail
    return out


def run_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
             rules: Optional[sh.ShardingRules] = None,
             unroll: bool = False, remat: bool = True,
             cfg_overrides: Optional[Dict[str, Any]] = None,
             verbose: bool = True) -> Dict[str, Any]:
    cfg = production_cfg(arch)
    if unroll:
        cfg = dataclasses.replace(cfg, unroll_layers=True)
    tuning = dict(TRAIN_TUNING.get(arch, {})) if shape_name.startswith(
        "train") else {}
    microbatches = tuning.pop("microbatches", TRAIN_MICROBATCHES)
    if tuning:
        cfg = dataclasses.replace(cfg, **tuning)
    if cfg_overrides:
        cfg = dataclasses.replace(cfg, **cfg_overrides)
    shape = SHAPES[shape_name]
    if not cell_applicable(cfg, shape):
        return {"arch": arch, "shape": shape_name, "status": "skipped",
                "reason": "long_500k needs sub-quadratic mixing "
                          "(DESIGN.md §5)"}
    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()
    lowered = lower_cell(cfg, shape, mesh, rules, remat=remat,
                         microbatches=microbatches)
    t1 = time.time()
    compiled = lowered.compile()
    t2 = time.time()

    mem = H.memory_per_device(compiled)
    # cost terms via finite-difference calibration (microbatches=1 so the
    # terms cover the FULL global batch; memory comes from the real
    # microbatched compile above).  See calibrated_costs docstring.
    cal = calibrated_costs(cfg, shape, mesh, rules, remat=remat)
    rl = H.Roofline(
        flops=cal["flops"], hbm_bytes=cal["hbm_bytes"],
        coll_bytes=cal["coll_bytes"], coll_detail=cal["coll_detail"],
        t_compute=cal["flops"] / TARGET_CHIP.flops,
        t_memory=cal["hbm_bytes"] / TARGET_CHIP.hbm_bw,
        t_collective=cal["coll_bytes"] / (TARGET_CHIP.ici_bw * 4),
        peak_flops=TARGET_CHIP.flops)
    n_chips = mesh.size
    # MODEL_FLOPS: 6 N D for train, 2 N D for inference (per token);
    # MoE uses active params.  Per-device = global / chips.
    n_active = cfg.n_params(active_only=True)
    tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode"
                                   else 1)
    mult = 6 if shape.kind == "train" else 2
    model_flops = mult * n_active * tokens / n_chips

    res = {
        "arch": arch, "shape": shape_name, "status": "ok",
        "mesh": tuple(mesh.shape.values()), "multi_pod": multi_pod,
        "lower_s": round(t1 - t0, 1), "compile_s": round(t2 - t1, 1),
        "bytes_per_device_gib": round(mem["total_gib"], 3),
        "flops_per_device": rl.flops,
        "hbm_bytes_per_device": rl.hbm_bytes,
        "collective_bytes": rl.coll_bytes,
        "collective_detail": {k: v for k, v in rl.coll_detail.items() if v},
        "t_compute_s": rl.t_compute,
        "t_memory_s": rl.t_memory,
        "t_collective_s": rl.t_collective,
        "dominant": rl.dominant,
        "model_flops_per_device": model_flops,
        "useful_flops_ratio": model_flops / max(rl.flops, 1.0),
        "roofline_fraction": rl.fraction(model_flops),
    }
    if verbose:
        print(f"[{arch} x {shape_name}] mesh={res['mesh']} "
              f"mem={res['bytes_per_device_gib']}GiB "
              f"compute={rl.t_compute*1e3:.2f}ms "
              f"memory={rl.t_memory*1e3:.2f}ms "
              f"collective={rl.t_collective*1e3:.2f}ms "
              f"dominant={rl.dominant} "
              f"roofline={res['roofline_fraction']:.3f}")
    return res


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--json", default=None)
    args = ap.parse_args(argv)

    cells = []
    if args.all:
        for a in ASSIGNED_ARCHS:
            for s in SHAPES:
                cells.append((a, s))
    else:
        assert args.arch and args.shape, "--arch and --shape, or --all"
        cells = [(args.arch, args.shape)]

    results = []
    failed = []
    for a, s in cells:
        try:
            results.append(run_cell(a, s, multi_pod=args.multi_pod))
        except Exception as e:  # noqa: BLE001 — report and continue sweep
            print(f"[{a} x {s}] FAILED: {type(e).__name__}: {e}",
                  file=sys.stderr)
            failed.append((a, s, f"{type(e).__name__}: {e}"))
            results.append({"arch": a, "shape": s, "status": "failed",
                            "error": f"{type(e).__name__}: {e}"})
    if args.json:
        with open(args.json, "w") as f:
            json.dump(results, f, indent=1, default=str)
    ok = sum(1 for r in results if r["status"] == "ok")
    sk = sum(1 for r in results if r["status"] == "skipped")
    print(f"\n{ok} ok, {sk} skipped, {len(failed)} failed "
          f"of {len(results)} cells")
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
