"""CPU tests of what a tensor-parallel cell needs of the benchmark: the
plain reference on a Phi-3-shaped model (RoPE, 4 queries per KV head,
SwiGLU, RMSNorm, an untied head), weights drawn into the serving
shardings, shares of a peak held to each chip's part of the work, the
collectives' exposed time, and a whole tp=4 cell added from files
alone.

The tp=4 checks run in one child process with four virtual CPU devices
(``tp4_probe.py``), since this process has one; the rest here.
"""
import json
import os
import subprocess
import sys
import types
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(ROOT))

from bench import correct, generator, harness, reference  # noqa: E402
from bench import trace as tr  # noqa: E402
from bench.peaks import peaks  # noqa: E402
from test_bench_harness import DEC  # noqa: E402
from tp4_probe import FAULTS, TINY_PHI3, TP, TP4_LIMIT, f32  # noqa: E402


# -- the reference on a Phi-3-shaped model, one device ----------------

def test_reference_matches_the_program_in_float32_on_a_phi3_shaped_model():
    import jax
    import jax.numpy as jnp
    from repro.models import transformer as T
    conf = {"model": f32(TINY_PHI3)}
    d = DEC.dims(conf)
    assert d["H"] // d["KV"] == 4 and d["rope"] and not d["tied"]
    seed = 2 ** 32 + 21
    params = DEC.make_params(seed, d)
    toks = np.random.default_rng(1).integers(0, d["V"], (2, 40))
    with jax.default_matmul_precision("highest"):
        prog, _ = T.forward(params, DEC.arch_config(conf, "xla"),
                            jnp.asarray(toks, jnp.int32))
    ref = DEC.logits(seed, d, toks.astype(np.int32))
    assert float(jnp.max(jnp.abs(ref))) > 1.0
    assert float(jnp.max(jnp.abs(prog - ref))) < 1e-5


def test_the_float8_control_fails_the_tp4_limit():
    """The float8 control on the Phi-3-shaped model, read teacher-forced
    at every position of a fixed sample, fails the tiny tp=4 cell's
    limit."""
    d = DEC.dims({"model": TINY_PHI3})
    rng = np.random.default_rng(4)
    rows = [(rng.integers(0, 128, 8).astype(np.int32),
             rng.integers(0, 128, 88).tolist(), 0) for _ in range(4)]
    limits = {"max_logit_gap": {"limit": TP4_LIMIT}}
    for seed in (51, 52, 53):
        out = correct.check(seed, DEC, d, rows, 4, 96, limits,
                            gaps=reference.control_gaps)
        assert not out["correct"], out["limits"]


def test_the_docqa_mix_fits_its_engine():
    """Every docqa request fits the 2,047 tokens the window of Phi-3's
    4k configuration attends to, and a slot of the engine."""
    mix = harness.load_json(ROOT, "bench", "traffic", "docqa.json")
    conf = harness.load_json(ROOT, "bench", "configs",
                             "phi3-medium-4k-tp4.json")
    waves, specs = generator.generate(mix, 2 ** 40 + 1, 51.0, 16, 32064)
    assert len(waves[0]) == conf["engine"]["slots"] == 16
    assert len(specs) == mix["requests"]
    for s in waves[0] + specs:
        assert 1024 <= len(s.prompt) <= 1792 and 64 <= s.max_new <= 255
        assert len(s.prompt) + s.max_new <= 2047 < conf["engine"]["max_len"]
    assert conf["published"]["sliding_window"] >= mix["max_total"]


# -- shares of a peak per chip, on hand-made traces -------------------

def _two_device_trace(collectives=True):
    """A 1 s window, two devices, one chunk step each: device 0 runs its
    program over [0.1, 0.5], device 1 over [0.1, 0.6].  Device 0: a
    matmul [0.10, 0.30], an all-reduce [0.30, 0.35] alone, a fusion
    [0.35, 0.45], an async all-reduce [0.40, 0.50] whose last 0.05 s
    runs alone, the layer loop around it all.  Device 1: the same ops,
    its all-reduces all under other ops, and one collective outside
    any step program."""
    loop = ("%while.1 = (s32[]) while(%t), body=%body", 0.10, 0.50)
    d0 = [loop, ("%fusion.1 = bf16[16,64,5120] fusion(%a)", 0.10, 0.30),
          ("%all-reduce.9 = bf16[16,64,5120] all-reduce(%f)", 0.30, 0.35),
          ("%fusion.2 = bf16[16,64,5120] fusion(%b)", 0.35, 0.45),
          ("%all-reduce-start.2 = bf16[16,64,5120] all-reduce-start(%c)",
           0.40, 0.50)]
    d1 = [("%fusion.1 = bf16[16,64,5120] fusion(%a)", 0.10, 0.60),
          ("%all-reduce.9 = bf16[16,64,5120] all-reduce(%f)", 0.30, 0.35),
          ("%all-gather.1 = bf16[8] all-gather(%g)", 0.70, 0.80)]
    if not collectives:
        d0, d1 = d0[:2] + d0[3:4], d1[:1]
    return tr.Trace(
        {"/device:TPU:0": {"ops": d0,
                           "modules": [("jit_chunk_fn(1)", 0.10, 0.50)]},
         "/device:TPU:1": {"ops": d1,
                           "modules": [("jit_chunk_fn(1)", 0.10, 0.60)]}},
        [(tr.WINDOW, 0.0, 1.0)])


def _tp_ctx(trace, chips):
    d = DEC.dims({"model": TINY_PHI3})
    calls = [{"kind": "chunk", "active": 2, "rows": [(0, 16), (10, 1)]}]
    return types.SimpleNamespace(trace=trace, calls=calls, family=DEC,
                                 dims=d, chips=chips, slots=4, chunk=16,
                                 notes=[],
                                 device_kind="TPU v5 lite")


def test_collective_exposed_on_a_hand_made_trace():
    ctx = _tp_ctx(_two_device_trace(), 2)
    got = harness.load_reader(ROOT, "collective_exposed").read(ctx)
    # device 0: 0.05 + 0.05 alone of 0.4 in its program; device 1: none
    assert got == pytest.approx(100 * (0.10 / 0.4 + 0.0) / 2, rel=1e-9)
    assert "[collective_exposed] per device 25.000%, 0.000%" in ctx.notes


def test_collective_exposed_finds_nothing_without_collectives():
    ctx = _tp_ctx(_two_device_trace(collectives=False), 2)
    assert harness.load_reader(ROOT, "collective_exposed").read(ctx) is None


@pytest.mark.parametrize("chips", [1, 2])
def test_chunk_step_mfu_holds_each_chip_to_its_share(chips):
    """Each device's time against the logical FLOPs over the chips,
    counted from the published heads; the mean over devices."""
    ctx = _tp_ctx(_two_device_trace(), chips)
    got = harness.load_reader(ROOT, "chunk_step_mfu").read(ctx)
    flops = DEC.chunk_step_flops(ctx.dims, [(0, 16), (10, 1)]) / chips
    pk = peaks("TPU v5 lite")["flops"]
    assert got == pytest.approx(
        100 * (flops / (0.4 * pk) + flops / (0.5 * pk)) / 2, rel=1e-9)


# -- tp=4 on four virtual devices, in a child process -----------------

@pytest.fixture(scope="module")
def tp4(tmp_path_factory):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    env.pop("PYTHONPATH", None)
    out = subprocess.run(
        [sys.executable, str(HERE / "tp4_probe.py"),
         str(tmp_path_factory.mktemp("tp4_root"))],
        env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_the_sharded_draw_equals_the_one_device_draw(tp4):
    draw = tp4["draw"]
    assert draw["leaves"] == 12 and draw["one_device"]
    assert draw["different"] == []
    # heads, d_ff and vocab over the four devices; K and V (3 heads)
    # and the norms stay whole until the executor pads the heads
    assert len(draw["split"]) == 7
    assert not any("'wk'" in p or "'wv'" in p for p in draw["split"])


def test_reference_matches_the_program_at_tp4_with_padded_kv_heads(tp4):
    fwd = tp4["forward"]
    assert fwd["kv_pad"] == 1
    assert fwd["ref_max_abs"] > 1.0
    assert fwd["max_abs_diff"] < 1e-4


def test_a_tp4_cell_added_from_files_alone_runs_and_is_correct(tp4):
    run = tp4["cells"]["sound"]
    assert run["correct"], run["checks"]
    assert run["device"]["count"] == TP
    assert run["checks"]["max_logit_gap"]["limit"] == TP4_LIMIT
    assert run["checks"]["tokens_compared"]["value"] > 0
    assert set(run["metrics"]) == {"output_tok_s", "itl_p95_ms",
                                   "ttft_p95_ms", "setup_s"}


@pytest.mark.parametrize("kind", FAULTS)
def test_a_broken_tp4_timed_path_is_not_correct(tp4, kind):
    run = tp4["cells"][kind]
    assert not run["correct"], run["checks"]
    assert run["checks"]["max_logit_gap"]["value"] > TP4_LIMIT
