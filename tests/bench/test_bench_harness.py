"""CPU tests of the chip benchmark's harness (``bench/``).

Nothing here measures speed.  The tests check what a CPU can: that a
seed fixes the traffic, that the FLOP and byte counts equal hand
counts, that each trace reader computes its number from a small trace
made by hand, that the command refuses to run without a TPU, that the
decoder family draws the same weights and reference logits bit for bit
as before it was a family, and — on a throwaway cell built from files
alone (configuration, mixes, an arrival process, an architecture), in
a temporary root, with the Pallas kernels in interpret mode — that a
whole run passes its check, that the float8 control fails it, and that
a broken timed path fails it.
"""
import hashlib
import json
import os
import shutil
import subprocess
import sys
import types
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import correct, generator, harness, model as bm  # noqa: E402
from bench import load_family, reference, trace as tr, work  # noqa: E402

TINY_MODEL = {
    "name": "tiny", "family": "dense", "n_layers": 2, "d_model": 64,
    "n_heads": 4, "n_kv_heads": 4, "head_dim": 16, "d_ff": 128,
    "vocab_size": 128, "rope": False, "learned_pos": True,
    "max_position": 128, "pattern": [["attn", "dense"]], "mlp_act": "gelu",
    "norm": "layernorm", "tie_embeddings": True,
    "param_dtype": "bfloat16", "compute_dtype": "bfloat16",
    "clover": {"enabled": True, "qk_rank": 8, "vo_rank": 8},
}
TINY_ENGINE = {"slots": 4, "max_len": 128, "paged": True, "page_tokens": 8,
               "n_pages": 96, "prefix_cache": True, "prefill_chunk": 16,
               "tp": 1}
TINY_MIXES = {
    "tinychat": {
        "arrivals": "poisson", "rate_per_s": 6.0, "size_seed": 7,
        "max_total": 96, "system_prompts": {"count": 2, "tokens": 24},
        "check_requests": 6,
        "classes": [
            {"share": 0.4, "system_prompt": True,
             "prompt": {"lo": 4, "hi": 24},
             "output": {"lo": 2, "hi": 8, "alpha": 1.2}},
            {"share": 0.2, "system_prompt": True,
             "prompt": {"lo": 0, "hi": 0},
             "output": {"lo": 2, "hi": 8, "alpha": 1.2}},
            {"share": 0.4, "prompt": {"lo": 8, "hi": 40},
             "output": {"lo": 2, "hi": 8, "alpha": 1.2}}]},
    "tinybacklog": {
        "arrivals": "backlog", "size_seed": 8, "requests": 40,
        "max_total": 96,
        "check_requests": 4,
        "initial_wave": {"prompt": {"lo": 8, "hi": 48},
                         "output": {"lo": 16, "hi": 40, "alpha": 1.2}},
        "classes": [{"share": 1.0, "prompt": {"lo": 8, "hi": 24},
                     "output": {"lo": 16, "hi": 40, "alpha": 1.2}}]},
}
# Set from CPU readings of the backlog cell, seeds 41-43, 85-96 served
# tokens compared: the bf16 engine's widest gap 0, 0, 0.0024; the
# float8 control's 0.035, 0.047, 0.044 there, and 0.041-0.059 over the
# 352 positions of the fixed sample below.
TINY_LIMIT = 0.012
SECONDS = 3.0
# the family of every configuration that names no ``arch``
DEC = load_family(ROOT, {})


def make_root(root: Path) -> Path:
    """A benchmark root holding a throwaway configuration, two mixes
    and two cells, written as files only; the metric readers, the
    families and the span list are the repository's own."""
    (root / "bench").mkdir()
    for sub in ("metrics", "arrivals", "families"):
        shutil.copytree(ROOT / "bench" / sub, root / "bench" / sub)
    shutil.copy(ROOT / "bench" / "spans.json", root / "bench")
    for sub in ("configs", "traffic", "limits"):
        (root / "bench" / sub).mkdir()
    conf = {"name": "tiny", "model": TINY_MODEL, "engine": TINY_ENGINE,
            "chips": 1}
    (root / "bench/configs/tiny.json").write_text(json.dumps(conf))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["configs"] = [{"name": "tiny", "source": "tests",
                         "file": "bench/configs/tiny.json", "reduced": [],
                         "why": "tiny"}]
    bench["workloads"] = []
    for mix, body in TINY_MIXES.items():
        (root / f"bench/traffic/{mix}.json").write_text(json.dumps(body))
        cell = f"tiny.{mix}"
        bench["workloads"].append({"name": cell, "config": "tiny",
                                   "traffic": mix, "chips": 1, "why": "t"})
        (root / f"bench/limits/{cell}.json").write_text(json.dumps(
            {"max_logit_gap": {"limit": TINY_LIMIT}}))
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [w["name"] for w in bench["workloads"]]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("bench_root"))


def _cell(root, name):
    bench = json.loads((root / "BENCHMARK.json").read_text())
    return next(w for w in bench["workloads"] if w["name"] == name)


def _run(root, name, trace=False, hooks=None, seed=2 ** 33 + 11):
    return harness.run_cell(_cell(root, name), seed, SECONDS, trace,
                            impl="interpret", root=root, hooks=hooks)


@pytest.fixture(scope="module")
def chat_run(tiny_root):
    return _run(tiny_root, "tiny.tinychat")


@pytest.fixture(scope="module")
def backlog_traced(tiny_root):
    return _run(tiny_root, "tiny.tinybacklog", trace=True)


# -- traffic ----------------------------------------------------------

@pytest.mark.parametrize("mix", sorted(TINY_MIXES)
                         + ["chat", "longgen", "docqa"])
def test_traffic_is_fixed_by_the_seed(mix):
    body = TINY_MIXES.get(mix) or json.loads(
        (ROOT / "bench" / "traffic" / f"{mix}.json").read_text())
    def specs(seed):
        waves, window = generator.generate(body, seed, 20.0, 4, 1000)
        return sum(waves, []) + window
    a, b, c = specs(2 ** 40 + 3), specs(2 ** 40 + 3), specs(5)
    for x, y in zip(a, b):
        assert x.uid == y.uid and x.max_new == y.max_new and x.due == y.due
        assert np.array_equal(x.prompt, y.prompt)
    # another seed: the same schedule (sizes, classes, arrival times,
    # in the same order) with other tokens
    def schedule(run):
        return [(len(s.prompt), s.max_new, s.due, s.cls) for s in run]
    assert schedule(a) == schedule(c)
    assert any(not np.array_equal(x.prompt, y.prompt)
               for x, y in zip(a, c))
    for s in a:
        assert len(s.prompt) + s.max_new <= body["max_total"]


def test_open_loop_shares_system_prompts():
    body = json.loads((ROOT / "bench/traffic/chat.json").read_text())
    waves, specs = generator.generate(body, 3, 30.0, 32, 50257)
    system = {tuple(s.prompt) for s in waves[0]}
    assert len(system) == body["system_prompts"]["count"]
    assert {tuple(s.prompt[:384]) for s in specs if s.cls == 0} == system
    # the system prompt alone: a full prefix hit, 24 whole pages, and
    # the set-up's second wave makes one before the window opens
    alone = [s for s in specs if s.cls == 1]
    assert alone and all(tuple(s.prompt) in system for s in alone)
    assert [tuple(s.prompt) for s in waves[1]] == [tuple(waves[0][0].prompt)]
    assert len(waves[0][0].prompt) % 16 == 0
    share = np.mean([s.cls in (0, 1) for s in specs])
    assert abs(share - 0.6) < 0.02
    assert len(specs) == round(body["rate_per_s"] * 30.0)
    assert all(0.0 < s.due < 30.0 for s in specs)


def test_an_arrival_process_added_as_a_file(tmp_path):
    """A later mix brings its own arrival process as a new file under
    ``bench/arrivals/``; the generator finds it by the mix's name."""
    (tmp_path / "bench" / "arrivals").mkdir(parents=True)
    (tmp_path / "bench" / "arrivals" / "steady.py").write_text(
        "import numpy as np\n"
        "def dues(mix, seconds, rng):\n"
        "    return np.arange(0.5, seconds, mix['every_s'])\n")
    body = dict(TINY_MIXES["tinychat"], arrivals="steady", every_s=2.0)
    _, specs = generator.generate(body, 1, 7.0, 4, 128, root=tmp_path)
    assert [s.due for s in specs] == [0.5, 2.5, 4.5, 6.5]


# -- work counts ------------------------------------------------------

def test_work_counts_equal_hand_counts():
    d = DEC.dims({"model": TINY_MODEL})
    D, H, dq, dv, F, V, L = 64, 4, 8, 8, 128, 128, 2
    per_layer = 2 * D * (H * dq * 2 + H * dv) + 2 * H * dv * D + 2 * 2 * D * F
    assert DEC.matmul_flops_per_token(d) == L * per_layer
    assert DEC.head_flops(d) == 2 * D * V
    assert DEC.kv_bytes_per_token(d) == L * H * (dq + dv) * 2
    layer_w = D * (H * dq * 2 + H * dv) + H * dv * D + 2 * D * F + 4 * D
    assert DEC.weight_bytes(d) == (L * layer_w + V * D) * 2
    flops, nbytes = DEC.decode_step(d, [5, 9])
    assert flops == 2 * (L * per_layer + 2 * D * V) \
        + L * 2 * H * (5 + 9) * (dq + dv)
    assert nbytes == DEC.weight_bytes(d) + 14 * L * H * (dq + dv) * 2
    # a row holding 3 tokens writes 2 more: contexts 4 and 5
    assert DEC.chunk_step_flops(d, [(3, 2), (0, 0)]) == \
        2 * L * per_layer + 2 * D * V + L * 2 * H * (4 + 5) * (dq + dv)
    kf, kb = DEC.kernel_work["paged_flash_decode"](d, [5, 9])
    assert kf == 2 * H * 14 * (dq + dv)
    assert kb == 14 * H * (dq + dv) * 2 + 2 * H * (dq + dv) * 2
    pk = {"flops": 1e12, "hbm_bw": 1e9}
    assert work.least_time(2e12, 1e9, pk) == (2.0, "flops")
    assert work.least_time(1e12, 3e9, pk) == (3.0, "bytes")


# -- trace readers ----------------------------------------------------

def _hand_trace():
    """Two decode steps and one chunk step in a 1 s window on one
    device.  Step programs: chunk [0.10, 0.30], decode [0.40, 0.50] and
    [0.60, 0.70]; each decode holds two kernel calls of 0.02 s."""
    ops = [("fusion.1", 0.10, 0.30), ("fusion.2", 0.40, 0.44),
           ("paged_decode", 0.44, 0.46), ("paged_decode", 0.46, 0.48),
           ("fusion.3", 0.48, 0.50), ("fusion.2", 0.60, 0.64),
           ("paged_decode", 0.64, 0.66), ("paged_decode", 0.66, 0.68),
           ("fusion.3", 0.68, 0.70)]
    modules = [("jit_chunk_fn(1)", 0.10, 0.30), ("jit_decode_fn(2)", 0.40,
                                                 0.50),
               ("jit_decode_fn(2)", 0.60, 0.70)]
    host = [(tr.WINDOW, 0.0, 1.0), ("Engine.step", 0.05, 0.35),
            ("exe.prefill_chunk", 0.06, 0.08), ("Engine.step", 0.38, 0.55),
            ("Engine.step", 0.58, 0.75), ("Engine._emit", 0.71, 0.74)]
    return tr.Trace({"/device:TPU:0": {"ops": ops, "modules": modules}},
                    host)


def _hand_ctx():
    d = DEC.dims({"model": TINY_MODEL})
    calls = [{"kind": "chunk", "active": 2, "rows": [(0, 16), (10, 1)]},
             {"kind": "decode", "active": 2, "held": [17, 12]},
             {"kind": "decode", "active": 1, "held": [18]}]
    return types.SimpleNamespace(trace=_hand_trace(), calls=calls,
                                 family=DEC, dims=d, slots=4, chunk=16,
                                 notes=[], chips=1,
                                 device_kind="TPU v5 lite",
                                 admitted_prompt_tokens=40, hit_tokens=16)


def _pk():
    from bench.peaks import peaks
    return peaks("TPU v5 lite")


@pytest.mark.parametrize("name,expect", [
    ("device_idle", lambda c: 100 * (1 - 0.4)),
    ("engine_host_ms", lambda c: 1e3 * ((0.30 - 0.20) + (0.17 - 0.10)
                                        + (0.17 - 0.10)) / 3),
    ("batch_occupancy", lambda c: 100 * 5 / 12),
    ("chunk_fill", lambda c: 100 * 17 / (4 * 16)),
    ("prefix_hit_share", lambda c: 100 * 16 / 40),
    ("decode_step_mfu", lambda c: 100 * (
        work.least_time(*DEC.decode_step(c.dims, [17, 12]), _pk())[0]
        + work.least_time(*DEC.decode_step(c.dims, [18]), _pk())[0]) / 0.2),
    ("chunk_step_mfu", lambda c: 100 * DEC.chunk_step_flops(
        c.dims, [(0, 16), (10, 1)]) / (0.2 * _pk()["flops"])),
    ("paged_decode_roofline", lambda c: 100 * 2 * (
        work.least_time(*DEC.paged_decode_call(c.dims, [17, 12]), _pk())[0]
        + work.least_time(*DEC.paged_decode_call(c.dims, [18]), _pk())[0])
        / 0.08),
])
def test_trace_reader_on_a_hand_made_trace(name, expect):
    ctx = _hand_ctx()
    got = harness.load_reader(ROOT, name).read(ctx)
    assert got == pytest.approx(expect(ctx), rel=1e-9)


def test_the_readers_take_the_familys_counts():
    """Every decode step of the hand-made trace is bound by its bytes,
    so a family that counts twice the bytes reads twice the share."""
    read = harness.load_reader(ROOT, "decode_step_mfu").read
    ctx = _hand_ctx()
    base = read(ctx)
    assert "bound by bytes in 2 of 2 decode steps" in ctx.notes[0]

    def doubled(d, held):
        flops, nbytes = DEC.decode_step(d, held)
        return flops, 2 * nbytes
    ctx = _hand_ctx()
    ctx.family = types.SimpleNamespace(decode_step=doubled)
    assert read(ctx) == pytest.approx(2 * base, rel=1e-12)


def test_breakdown_names_ops_and_idle_spans():
    out = tr.breakdown(_hand_trace())
    ops = dict(out["device_ops"])
    assert ops["fusion"] == pytest.approx(0.2 + 0.04 * 2 + 0.02 * 2)
    assert ops["paged_decode"] == pytest.approx(0.08)
    idle = dict(out["idle_gaps"])
    # 0.6 s idle: 0.02 in the chunk call's span, 0.18 in the rest of
    # three steps, 0.03 in _emit, 0.36 outside any span
    assert idle["exe.prefill_chunk"] == pytest.approx(0.02)
    assert idle["Engine._emit"] == pytest.approx(0.03)
    assert idle["Engine.step"] == pytest.approx(0.08 + 0.07 + 0.04)
    assert idle["outside any span"] == pytest.approx(0.36)
    assert sum(idle.values()) == pytest.approx(0.6)
    assert tr.busy(_hand_trace()) == pytest.approx((0.4, 1.0))


def test_output_rate_counts_the_last_step_whole():
    """The window's last step ends past ``seconds`` (t1 = 2.0): its
    tokens and its time both count; a token from set-up does not."""
    reqs = [types.SimpleNamespace(token_times=[0.5, 1.0, 2.1]),
            types.SimpleNamespace(token_times=[-0.2, 1.0, 2.1])]
    ctx = types.SimpleNamespace(requests=reqs, t0=0.0, t1=2.0, t_close=2.1,
                                seconds=2.0)
    got = harness.load_reader(ROOT, "output_tok_s").read(ctx)
    assert got == pytest.approx(5 / 2.1, rel=1e-12)


def test_itl_leaves_out_the_gap_from_set_up_into_the_window():
    """A first-wave request's last set-up token (-0.3) to its first
    window token (0.2) spans set-up work: left out.  Inside: 0.2 -> 0.6
    -> 1.1, then a gap still open at the window's end (t1 = 2.0)."""
    reqs = [types.SimpleNamespace(token_times=[-0.6, -0.3, 0.2, 0.6, 1.1],
                                  status="RUNNING"),
            types.SimpleNamespace(token_times=[0.5, 0.7], status="DONE")]
    ctx = types.SimpleNamespace(requests=reqs, t0=0.0, t1=2.0, notes=[])
    got = harness.load_reader(ROOT, "itl_p95_ms").read(ctx)
    gaps = [0.4, 0.5, 0.9, 0.2]
    assert got == pytest.approx(1e3 * np.percentile(gaps, 95), rel=1e-12)


def test_a_reader_with_nothing_to_read_returns_none():
    ctx = types.SimpleNamespace(trace=None, calls=[], notes=[], slots=4,
                                chunk=16, admitted_prompt_tokens=0,
                                hit_tokens=0, device_kind="TPU v5 lite")
    for m in json.loads((ROOT / "BENCHMARK.json").read_text())["per_layer"]:
        assert harness.load_reader(ROOT, m["name"]).read(ctx) is None


# -- the reference and the weights ------------------------------------

def test_reference_matches_the_program_in_float32():
    import jax
    import jax.numpy as jnp
    from repro.models import transformer as T
    conf = {"model": dict(TINY_MODEL, param_dtype="float32",
                          compute_dtype="float32")}
    d = DEC.dims(conf)
    seed = 2 ** 32 + 9
    params = DEC.make_params(seed, d)
    one = DEC.layer_params(bm.layer_key(seed, 1), d, 1)
    for a, b in zip(jax.tree.leaves(params["blocks"][0]),
                    jax.tree.leaves(one)):
        assert np.array_equal(np.asarray(a[1]), np.asarray(b))
    toks = np.random.default_rng(0).integers(0, d["V"], (2, 24))
    with jax.default_matmul_precision("highest"):
        prog, _ = T.forward(params, DEC.arch_config(conf, "xla"),
                            jnp.asarray(toks, jnp.int32))
    ref = DEC.logits(seed, d, toks.astype(np.int32))
    assert float(jnp.max(jnp.abs(prog - ref))) < 1e-5


# sha256 of every weight leaf's bytes (tree order) and of the float32
# and float8 reference logits of two rows of 24 tokens, seed 41, as
# ``bench/model.py`` and ``bench/reference.py`` gave them before the
# decoder became a family
PARENT_HASHES = {
    "tiny": {
        "weights": "a1b2bf7e28373bd14dd89aca72adab710af3e4bfad0f553933239244"
                   "fc1a8fa2",
        "f32": "a50dae4e9db0bb913535010e3ccee8b7b37226624d76ededb4609aed3fe6"
               "6ca2",
        "fp8": "6faf884957cb8e7ad61a4d0d927887928fed64fc9ac8ab1fe0afcbc54bc9"
               "48ed"},
    "tiny-phi3": {
        "weights": "98be3d272da7b1d638aa115dad9e385980e20caae5812a9bc6a9fe4d"
                   "92626d07",
        "f32": "9a1cc26f5c32d3bee2c3cb4c5628bf1e1ef5508636a0eb0d33db0e8806636"
               "c62",
        "fp8": "54ffeea1afcd3869773ffd9abe8fe662506c3e997e29a3422110265c1b40"
               "4cf7"},
}


@pytest.mark.parametrize("name", sorted(PARENT_HASHES))
def test_the_decoder_family_draws_and_computes_as_before(name):
    import jax
    if name == "tiny":
        model = TINY_MODEL
    else:
        sys.path.insert(0, str(Path(__file__).resolve().parent))
        from tp4_probe import TINY_PHI3 as model
    d = DEC.dims({"model": model})
    h = hashlib.sha256()
    for leaf in jax.tree.leaves(DEC.make_params(41, d)):
        h.update(np.asarray(leaf).tobytes())
    got = {"weights": h.hexdigest()}
    toks = np.random.default_rng(0).integers(0, d["V"], (2, 24))
    for precision in ("f32", "fp8"):
        out = DEC.logits(41, d, toks.astype(np.int32), precision=precision)
        got[precision] = hashlib.sha256(
            np.asarray(out, np.float32).tobytes()).hexdigest()
    assert got == PARENT_HASHES[name]


# -- the command ------------------------------------------------------

def _command(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "gpt2xl-r32.chat",
         "--seed", str(2 ** 33), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_command_refuses_without_a_tpu():
    out = _command(ROOT)
    assert out.returncode == 2, out.stderr
    assert "needs a TPU" in out.stderr
    assert not out.stdout.strip()


def test_command_refuses_in_a_checkout_of_the_benchmark_alone(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in json.loads((ROOT / "BENCHMARK.json").read_text())["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    out = _command(tmp_path)
    assert out.returncode != 0
    assert not out.stdout.strip()


# -- whole runs of a throwaway cell (interpret kernels) ---------------

def test_a_cell_added_from_files_alone_runs_and_is_correct(chat_run):
    assert chat_run["correct"], chat_run["checks"]
    m = chat_run["metrics"]
    assert set(m) == {"output_tok_s", "itl_p95_ms", "ttft_p95_ms",
                      "setup_s"}
    assert all(v["value"] > 0 for v in m.values())
    assert chat_run["attempted"] == round(6.0 * SECONDS)
    assert chat_run["failed"] == 0
    assert chat_run["device"]["platform"] == "cpu"
    assert list(chat_run)[-1] == "checks"
    lim = chat_run["checks"]["max_logit_gap"]
    assert lim["limit"] == TINY_LIMIT and lim["value"] <= TINY_LIMIT


# a family added as a new file: the decoder, with a size of its own
TOY_FAMILY = '''"""The decoder, with one size of its own in its dims."""
from pathlib import Path

from bench import load_module

_dec = load_module(Path(__file__).resolve().parents[2], "families",
                   "decoder")
arch_config, build, layer_params, top_params, make_params, logits = (
    _dec.arch_config, _dec.build, _dec.layer_params, _dec.top_params,
    _dec.make_params, _dec.logits)
decode_step, chunk_step_flops, weight_bytes, kv_bytes_per_token = (
    _dec.decode_step, _dec.chunk_step_flops, _dec.weight_bytes,
    _dec.kv_bytes_per_token)
kernel_work = _dec.kernel_work


def dims(conf):
    return dict(_dec.dims(conf), toy=conf["toy"])
'''


def test_an_architecture_added_as_a_file(tmp_path, capsys):
    """A configuration that names its own ``arch`` is served, checked
    against its family's reference and read by the metric readers,
    with new files alone; one naming an absent family is refused with
    the path looked for, before anything is drawn."""
    root = make_root(tmp_path)
    (root / "bench/families/toy.py").write_text(TOY_FAMILY)
    (root / "bench/metrics/toy_size.py").write_text(
        "def read(ctx):\n    return ctx.dims['toy']\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    for arch, cell in (("toy", "toy.tinybacklog"),
                       ("absent", "absent.tinybacklog")):
        conf = {"name": arch, "arch": arch, "toy": 7, "model": TINY_MODEL,
                "engine": TINY_ENGINE, "chips": 1}
        (root / f"bench/configs/{arch}.json").write_text(json.dumps(conf))
        (root / f"bench/limits/{cell}.json").write_text(json.dumps(
            {"max_logit_gap": {"limit": TINY_LIMIT}}))
        bench["configs"].append({"name": arch, "source": "tests",
                                 "file": f"bench/configs/{arch}.json",
                                 "reduced": [], "why": arch})
        bench["workloads"].append({"name": cell, "config": arch,
                                   "traffic": "tinybacklog", "chips": 1,
                                   "why": "t"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"].append("toy.tinybacklog")
    bench["per_layer"].append({"name": "toy_size", "unit": "1",
                               "better": "higher", "source": "program_counter",
                               "layer": "toy", "moves": "output_tok_s",
                               "workloads": ["toy.tinybacklog"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    run = _run(root, "toy.tinybacklog", trace=True)
    assert run["correct"], run["checks"]
    assert run["checks"]["tokens_compared"]["value"] > 0
    assert run["metrics"]["toy_size"]["value"] == 7
    assert 0 < run["metrics"]["batch_occupancy"]["value"] <= 100

    with pytest.raises(SystemExit) as out:
        harness.open_cell(root, "absent.tinybacklog", "test")
    assert out.value.code == 2
    assert "bench/families/absent.py" in capsys.readouterr().err


def test_a_traced_run_reports_counters_and_no_device_numbers_on_cpu(
        backlog_traced):
    assert backlog_traced["correct"]
    # the first wave is served inside the window, so it counts
    assert backlog_traced["attempted"] >= TINY_ENGINE["slots"]
    m = backlog_traced["metrics"]
    # the CPU has no device plane: every device number is left out
    assert set(m) == {"batch_occupancy", "chunk_fill", "prefix_hit_share"}
    assert 0 < m["batch_occupancy"]["value"] <= 100
    assert "busy_s" not in backlog_traced["device"]


def test_the_float8_control_fails_the_limit():
    """The control: the reference computed in float8 (the precision
    below the configuration's bfloat16) in the program's place, read
    teacher-forced at every position of a fixed sample, goes through
    the same check and comes out not correct."""
    d = DEC.dims({"model": TINY_MODEL})
    rng = np.random.default_rng(3)
    rows = [(rng.integers(0, 128, 8).astype(np.int32),
             rng.integers(0, 128, 88).tolist(), 0) for _ in range(4)]
    limits = {"max_logit_gap": {"limit": TINY_LIMIT}}
    for seed in (41, 42, 43):
        out = correct.check(seed, DEC, d, rows, 4, 96, limits,
                            gaps=reference.control_gaps)
        assert out["compared"] == 4 * 88
        assert not out["correct"], out["limits"]
        assert out["limits"]["max_logit_gap"]["value"] > TINY_LIMIT


def _break(kind):
    def hook(eng):
        if kind == "token_altered":
            sample = eng._sample
            eng._sample = lambda logits, t: (sample(logits, t) + 1) % 128
        elif kind == "state_unchanged":
            step = eng.exe.decode_step

            def stale(state, *a, **k):
                logits, _ = step(state, *a, **k)
                return logits, state
            eng.exe.decode_step = stale
        elif kind == "copy_skipped":
            # the copy-on-write page copy returns the pool unchanged
            eng.exe.page_copy = lambda state, src, dst: state
    return {"engine": hook}


@pytest.mark.parametrize("kind,cell", [
    ("token_altered", "tiny.tinybacklog"),
    ("state_unchanged", "tiny.tinybacklog"),
    ("copy_skipped", "tiny.tinychat")])
def test_a_broken_timed_path_is_not_correct(tiny_root, kind, cell):
    run = _run(tiny_root, cell, hooks=_break(kind), seed=43)
    assert not run["correct"], run["checks"]

