"""The tensor-parallel checks of ``test_bench_tp.py``, in a process of
their own with four virtual CPU devices:

    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \\
        python tests/bench/tp4_probe.py <empty directory>

On a tiny Phi-3-shaped model whose 3 KV heads 4 does not divide (the
executor pads them to 4): the weights drawn into the serving shardings
against the one-device draw, the program's float32 forward pass over the
sharded, padded weights against the plain reference, and whole runs of
a tp=4 cell added from files alone (interpret-mode kernels): a sound
one, and one for each fault of the timed path that such a cell can
have.  Prints one JSON line.
"""
import dataclasses
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src"), str(HERE)]

import numpy as np  # noqa: E402

from test_bench_harness import (DEC, SECONDS, TINY_ENGINE,  # noqa: E402
                                make_root)

TINY_PHI3 = {
    "name": "tiny-phi3", "family": "dense", "n_layers": 2, "d_model": 64,
    "n_heads": 12, "n_kv_heads": 3, "head_dim": 16, "d_ff": 128,
    "vocab_size": 128, "rope": True, "rope_theta": 10000.0,
    "learned_pos": False, "max_position": 128,
    "pattern": [["attn", "dense"]], "mlp_act": "swiglu", "norm": "rmsnorm",
    "norm_eps": 1e-5, "tie_embeddings": False,
    "param_dtype": "bfloat16", "compute_dtype": "bfloat16",
    "clover": {"enabled": True, "qk_rank": 16, "vo_rank": 16},
}
TP = 4
CELL = "tiny4.tinybacklog"
# Set from CPU readings of this cell on seeds 51-56, 2**33 + 11 and
# 2**40 + 5: the program's widest gap 0-0.0183, the float8 control's
# 0.244-0.618 on the same served requests.
TP4_LIMIT = 0.06
FAULTS = ("token_altered", "state_unchanged", "exchange_left_out")


def f32(model: dict) -> dict:
    return dict(model, param_dtype="float32", compute_dtype="float32")


def check_draw(seed: int) -> dict:
    """Every leaf of the sharded draw equals the one-device draw."""
    import jax
    d = DEC.dims({"model": TINY_PHI3})
    one = DEC.make_params(seed, d)
    four = DEC.make_params(seed, d, TP)
    paths = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(one)[0]]
    a, b = jax.tree.leaves(one), jax.tree.leaves(four)
    return {"leaves": len(a),
            "different": [p for p, x, y in zip(paths, a, b)
                          if not np.array_equal(np.asarray(x),
                                                np.asarray(y))],
            "one_device": all(len(x.sharding.device_set) == 1 for x in a),
            "split": [p for p, y in zip(paths, b)
                      if not y.sharding.is_fully_replicated]}


def check_forward(seed: int) -> dict:
    """The program's float32 forward pass over the weights as the
    sharded executor holds them (KV heads padded 3 -> 4, every leaf on
    its shards) against the reference on the published 3 heads."""
    import jax
    import jax.numpy as jnp
    from bench import model as bm
    from repro.models import transformer as T
    from repro.parallel.sharding import use_mesh
    from repro.serve.executor import ShardedExecutor
    conf = {"model": f32(TINY_PHI3), "engine": dict(TINY_ENGINE, tp=TP)}
    d = DEC.dims(conf)
    exe = ShardedExecutor(DEC.make_params(seed, d, TP),
                          DEC.arch_config(conf, "xla"),
                          bm.engine_config(conf, "xla"))
    toks = np.random.default_rng(seed).integers(0, d["V"], (2, 24))
    with use_mesh(exe.mesh), jax.default_matmul_precision("highest"):
        prog, _ = T.forward(exe.params, dataclasses.replace(
            exe.cfg, kernel_impl="xla"), jnp.asarray(toks, jnp.int32))
    ref = DEC.logits(seed, d, toks.astype(np.int32))
    return {"kv_pad": exe.kv_pad,
            "max_abs_diff": float(jnp.max(jnp.abs(prog - ref))),
            "ref_max_abs": float(jnp.max(jnp.abs(ref)))}


def add_tp4_cell(root: Path) -> Path:
    """The tiny benchmark root of ``test_bench_harness`` with one more
    configuration and cell, tp=4 on four chips, as files only."""
    conf = {"name": "tiny4", "model": TINY_PHI3,
            "engine": dict(TINY_ENGINE, tp=TP), "chips": TP}
    (root / "bench/configs/tiny4.json").write_text(json.dumps(conf))
    (root / f"bench/limits/{CELL}.json").write_text(json.dumps(
        {"max_logit_gap": {"limit": TP4_LIMIT}}))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny4", "source": "tests",
                             "file": "bench/configs/tiny4.json",
                             "reduced": [], "why": "tiny tp=4"})
    bench["workloads"].append({"name": CELL, "config": "tiny4",
                               "traffic": "tinybacklog", "chips": TP,
                               "why": "t"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"].append(CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def fault(kind: str):
    """An ``engine`` hook that breaks the timed path underneath."""
    def hook(eng):
        exe = eng.exe
        if kind == "token_altered":
            sample = eng._sample
            eng._sample = lambda logits, t: (sample(logits, t) + 1) % 128
        elif kind == "state_unchanged":
            step = exe.decode_step

            def stale(state, *a, **k):
                logits, _ = step(state, *a, **k)
                return logits, state
            exe.decode_step = stale
        elif kind == "exchange_left_out":
            # each layer's attention and MLP output keeps the first
            # shard's partial sum alone, as a device that skipped the
            # all-reduce would: the other shards' rows of the
            # row-parallel weights are zeroed
            blocks = dict(exe.params["blocks"][0])
            attn, mlp = dict(blocks["attn"]), dict(blocks["mlp"])
            h = attn["wo"].shape[1] // TP
            f = mlp["w_down"].shape[1] // TP
            attn["wo"] = attn["wo"].at[:, h:].set(0)
            mlp["w_down"] = mlp["w_down"].at[:, f:].set(0)
            blocks.update(attn=attn, mlp=mlp)
            exe.params = dict(exe.params, blocks=(blocks,))
    return {"engine": hook}


def run_cells(root: Path, seed: int) -> dict:
    from bench import harness
    root = add_tp4_cell(make_root(root))
    cell = next(w for w in harness.load_json(root, "BENCHMARK.json")
                ["workloads"] if w["name"] == CELL)
    out = {}
    for kind in ("sound",) + FAULTS:
        # a fault shows in a short window
        res = harness.run_cell(
            cell, seed, SECONDS if kind == "sound" else SECONDS / 3, False,
            impl="interpret", root=root,
            hooks=fault(kind) if kind != "sound" else None)
        out[kind] = {"correct": res["correct"], "checks": res["checks"],
                     "device": res["device"], "metrics": res["metrics"]}
    return out


def main(argv):
    import jax
    assert jax.device_count() == TP, jax.device_count()
    seed = 2 ** 33 + 11
    print(json.dumps({"draw": check_draw(seed),
                      "forward": check_forward(seed),
                      "cells": run_cells(Path(argv[0]), seed)}))


if __name__ == "__main__":
    main(sys.argv[1:])
