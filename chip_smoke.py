"""Bring-up smoke: the CLOVER serving engine on a TPU, end to end.

Default (one chip): GPT-2 XL at its published width (48 layers, d_model
1600, 25 heads of 64, vocab 50257) in bf16 with random weights from
``--seed``, CLOVER-decomposed and pruned to half rank, served by the
paged, prefix-cached, continuous-batching ``Engine`` with
``kernel_impl="pallas"``: 16 requests, prompts of 32-512 tokens, 32-64
new tokens each.  The same traffic then runs on the same engine with
``kernel_impl="xla"``, and the decode logits of the two are compared.

``--chips 4``: only the tensor-parallel phase — phi3-medium-14b (about
28 GB in bf16, more than one chip holds) at ``tp=4`` with the Pallas
kernels, compared with a teacher-forced full forward pass over the
same sharded weights.

The last line of stdout is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``;
it is printed only when every phase passed.  Without a TPU the script
exits 2 before doing anything.

Run:  python chip_smoke.py            # one chip
      python chip_smoke.py --chips 4  # four chips, tensor parallel
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

# Bound on max|logits - reference| / max|reference| per compared row.
# Both sides store weights, activations and the KV cache in bf16, whose
# 8 significant bits give a relative step of 2**-8; they round at
# different points (the Pallas kernels keep attention scores and
# probabilities in f32 and normalise once; the XLA path rounds both to
# bf16; a full forward pass rounds a whole prompt at once where the
# engine prefills in chunks).  Those roundings compound through the
# residual stream of 40-48 layers, so an honest difference costs a few
# of the 8 bits: measured on CPU at 16 layers, 2**-5.8 (engine vs
# engine) and 2**-5.4 (engine vs forward pass) of the largest logit.
# 2**-3 leaves room for three times the depth; a kernel bug (wrong
# page, head or mask) moves logits by their whole spread, ~2**0.
LOGIT_REL_TOL = 2.0 ** -3


class Failure(Exception):
    """A phase's result is wrong (as opposed to a phase that raised)."""


def _tree_bytes(tree) -> int:
    import jax
    return sum(int(x.nbytes) for x in jax.tree.leaves(tree))


def _kv_bytes(state) -> int:
    import jax
    total = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(
            state["blocks"])[0]:
        if any(getattr(p, "key", None) == "kv" for p in path):
            total += int(leaf.nbytes)
    return total


def _per_device_bytes(tree) -> dict:
    import jax
    out: dict = {}
    for leaf in jax.tree.leaves(tree):
        for shard in leaf.addressable_shards:
            key = str(shard.device.id)
            out[key] = out.get(key, 0) + int(shard.data.nbytes)
    return dict(sorted(out.items()))


class CompileLog:
    """Backend compile seconds and persistent-cache hits, from JAX's
    own monitoring events."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.compiles += 1

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def snapshot(self) -> dict:
        return {"backend_compile_s": round(self.seconds, 3),
                "compiles": self.compiles,
                "cache_hits": self.cache_hits,
                "cache_misses": self.cache_misses}


def make_requests(vocab: int, seed: int, n: int, prompt_range, new_range,
                  page_tokens: int):
    """``n`` requests with mixed prompt lengths.  Three share a 256-token
    system prompt (prefix-cache hits), and the last repeats the first
    request's page-aligned prompt, so its admission maps every page of
    it and copies the last one on write (the ``page_copy`` kernel)."""
    from repro.serve import Request
    rng = np.random.default_rng(seed)
    lo, hi = prompt_range
    system = rng.integers(0, vocab, min(256, hi // 2)).astype(np.int32)
    prompts = []
    for i in range(n - 1):
        if i in (2, 5, 9):
            tail = rng.integers(0, vocab, int(rng.integers(8, 33)))
            prompts.append(np.concatenate([system, tail.astype(np.int32)]))
        else:
            L = int(rng.integers(lo, hi + 1))
            if i == 0:
                L = max(page_tokens, L // page_tokens * page_tokens)
            prompts.append(rng.integers(0, vocab, L).astype(np.int32))
    prompts.append(prompts[0].copy())
    return [Request(uid=i, prompt=p,
                    max_new_tokens=int(rng.integers(new_range[0],
                                                    new_range[1] + 1)))
            for i, p in enumerate(prompts)]


def serve(params, cfg, ecfg, requests):
    """Run ``requests`` through a fresh ``Engine``; record every emitted
    token's logits row and which compiled step produced it."""
    import jax
    from repro.serve import Engine, Request
    eng = Engine(params, cfg, ecfg)
    exe = eng.exe
    last = {"kind": None}

    def tap(kind, fn):
        def call(*a, **k):
            last["kind"] = kind
            return fn(*a, **k)
        return call

    exe.decode_step = tap("decode", exe.decode_step)
    exe.prefill_chunk = tap("chunk", exe.prefill_chunk)
    emitted: dict = {}
    emit = eng._emit

    def record(slots, logits):
        for s in slots:
            uid = eng.sched.slot_req[s].uid
            emitted.setdefault(uid, []).append(
                (last["kind"], np.array(logits[s], np.float32)))
        emit(slots, logits)

    eng._emit = record
    reqs = [Request(uid=r.uid, prompt=r.prompt,
                    max_new_tokens=r.max_new_tokens) for r in requests]
    t0 = time.perf_counter()
    eng.run(reqs)
    jax.block_until_ready(eng.state)
    wall = time.perf_counter() - t0
    for r in reqs:
        if r.status != "DONE" or len(r.generated) != r.max_new_tokens:
            raise Failure(f"request {r.uid} ended {r.status} with "
                          f"{len(r.generated)}/{r.max_new_tokens} tokens")
        if not all(0 <= t < cfg.vocab_size for t in r.generated):
            raise Failure(f"request {r.uid}: token outside the vocab")
    out = {
        "tokens": {r.uid: list(r.generated) for r in reqs},
        "emitted": emitted,
        "wall_s": wall,
        "steps": eng.steps,
        "kernel_report": exe.kernel_report(),
        "compiled_shapes": eng.compiled_shapes(),
        "kv_pool_bytes": _kv_bytes(eng.state),
        "param_bytes": _tree_bytes(exe.params),
        "prefix_hits": eng.sched.prefix_hits,
        "executor": exe,
    }
    return eng, out


def check_kernels(report: dict, entries, impl: str) -> None:
    """Every named entry must have run the ``impl`` kernels (``pallas``
    on the chip) — never the interpreter, the oracle or the XLA path."""
    for entry in entries:
        ran = report.get(entry, "missing")
        if not ran.startswith(impl):
            raise Failure(f"{entry} ran {ran!r}, not {impl!r}")


def compare_streams(a: dict, b: dict, *, teacher_forced: bool = False,
                    kinds=("decode",)) -> dict:
    """Logits of run ``a`` against run ``b`` for every emission of the
    given kinds.  Two free-running engines are compared up to each
    request's first token disagreement (until then both fed every step
    the same tokens); a ``teacher_forced`` reference saw the engine's
    own tokens, so every position is compared."""
    max_abs = max_rel = 0.0
    compared = agree = total = same = 0
    for uid, toks in a["tokens"].items():
        other = b["tokens"][uid]
        n = len(toks)
        first = next((i for i in range(n) if toks[i] != other[i]), n)
        agree += (sum(int(x == y) for x, y in zip(toks, other))
                  if teacher_forced else first)
        total += n
        same += first == n
        last = n if teacher_forced else min(first + 1, n)
        for i in range(last):
            kind, la = a["emitted"][uid][i]
            if kind not in kinds:
                continue
            lb = b["emitted"][uid][i][1]
            diff = float(np.max(np.abs(la - lb)))
            max_abs = max(max_abs, diff)
            max_rel = max(max_rel, diff / float(np.max(np.abs(lb))))
            compared += 1
    return {"max_abs_logit_diff": max_abs, "max_rel_logit_diff": max_rel,
            "logit_rows_compared": compared, "tokens_agreeing": agree,
            "tokens_total": total, "requests_identical": same,
            "requests": len(a["tokens"])}


def report_and_gate(label: str, cmp: dict) -> None:
    print(f"[compare] {label}: max |diff| {cmp['max_abs_logit_diff']:.6f}, "
          f"max |diff|/max|ref| {cmp['max_rel_logit_diff']:.6f} over "
          f"{cmp['logit_rows_compared']} decode rows (tolerance "
          f"{LOGIT_REL_TOL}); greedy tokens agreeing "
          f"{cmp['tokens_agreeing']}/{cmp['tokens_total']}, identical "
          f"streams {cmp['requests_identical']}/{cmp['requests']}")
    if cmp["logit_rows_compared"] == 0:
        raise Failure(f"{label}: no decode-step logits were compared")
    if not cmp["max_rel_logit_diff"] <= LOGIT_REL_TOL:
        raise Failure(f"{label}: logits differ by "
                      f"{cmp['max_rel_logit_diff']} of their scale > "
                      f"{LOGIT_REL_TOL}")


def one_chip(seed: int, log: CompileLog, *, arch: str = "gpt2-xl",
             impl: str = "pallas", n_requests: int = 16,
             prompt_range=(32, 512), new_range=(32, 64), slots: int = 8,
             max_len: int = 1024, n_pages: int = 1024,
             reduced: bool = False) -> dict:
    """GPT-2 XL (CLOVER-pruned, bf16) through the paged engine with the
    Pallas kernels, then the same traffic with ``kernel_impl="xla"``."""
    import jax
    from repro.configs import get_config
    from repro.core import clover_decompose, clover_prune
    from repro.models import init_lm_params
    from repro.serve import EngineConfig

    cfg = get_config(arch)
    if reduced:
        cfg = cfg.reduced()
    cfg = dataclasses.replace(cfg, param_dtype="bfloat16",
                              compute_dtype="bfloat16")
    print(f"[config] {cfg.name}: {cfg.n_layers} layers, d_model "
          f"{cfg.d_model}, {cfg.n_heads} heads x {cfg.head_dim_}, vocab "
          f"{cfg.vocab_size}, bf16 params and compute, seed {seed}")
    t0 = time.perf_counter()
    params = jax.jit(init_lm_params, static_argnums=0)(
        cfg, jax.random.PRNGKey(seed))
    dparams, dcfg, _ = clover_decompose(params, cfg, peft=False)
    del params
    pparams, pcfg = clover_prune(dparams, dcfg, qk_ratio=0.5, vo_ratio=0.5)
    pparams = jax.block_until_ready(pparams)
    del dparams
    print(f"[prune] CLOVER ratio 0.5: qk rank {pcfg.qk_dim}, vo rank "
          f"{pcfg.vo_dim} (head_dim {cfg.head_dim_}); params "
          f"{_tree_bytes(pparams)} bytes; init+decompose+prune "
          f"{time.perf_counter() - t0:.3f} s")
    requests = make_requests(cfg.vocab_size, seed, n_requests, prompt_range,
                             new_range, page_tokens=16)
    print(f"[traffic] {len(requests)} requests, prompt tokens "
          f"{[len(r.prompt) for r in requests]}, new tokens "
          f"{[r.max_new_tokens for r in requests]}")
    ecfg = EngineConfig(slots=slots, max_len=max_len, paged=True,
                        page_tokens=16, n_pages=n_pages, prefix_cache=True,
                        kernel_impl=impl)
    runs = {}
    for name in (impl, "xla"):
        before = log.snapshot()
        eng, out = serve(pparams, pcfg,
                         dataclasses.replace(ecfg, kernel_impl=name),
                         requests)
        comp = log.snapshot()
        dev = jax.devices()[0]
        mem = dev.memory_stats() or {}
        print(f"[serve:{name}] kernel_report {out['kernel_report']}")
        print(f"[serve:{name}] compiled_shapes {out['compiled_shapes']}, "
              f"engine steps {out['steps']}, prefix hits "
              f"{out['prefix_hits']}, wall {out['wall_s']:.3f} s "
              "(compile included)")
        print(f"[serve:{name}] backend compile "
              f"{comp['backend_compile_s'] - before['backend_compile_s']:.3f}"
              f" s in {comp['compiles'] - before['compiles']} compiles; "
              f"persistent cache hits "
              f"{comp['cache_hits'] - before['cache_hits']}, misses "
              f"{comp['cache_misses'] - before['cache_misses']}")
        print(f"[memory:{name}] params {out['param_bytes']} bytes + KV pool "
              f"{out['kv_pool_bytes']} bytes = "
              f"{out['param_bytes'] + out['kv_pool_bytes']} bytes; device "
              f"peak_bytes_in_use {mem.get('peak_bytes_in_use')} of "
              f"bytes_limit {mem.get('bytes_limit')}")
        if name == impl:
            check_kernels(out["kernel_report"], ("decode_step", "page_copy"),
                          impl)
            if not out["compiled_shapes"] or out["compiled_shapes"] < 3:
                raise Failure("no copy-on-write page copy ran "
                              f"(compiled_shapes {out['compiled_shapes']})")
        out.pop("executor")
        runs[name] = out
        del eng
        gc.collect()
    cmp = compare_streams(runs[impl], runs["xla"])
    report_and_gate(f"{impl} vs xla engine", cmp)
    return cmp


def four_chips(seed: int, log: CompileLog, *, arch: str = "phi3-medium-14b",
               impl: str = "pallas", tp: int = 4, n_requests: int = 8,
               prompt_range=(32, 256), new_range=(16, 32),
               reduced: bool = False) -> dict:
    """phi3-medium-14b (bf16) at tensor-parallel degree ``tp`` with the
    Pallas kernels against a teacher-forced forward pass over the same
    sharded weights."""
    import jax
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.launch.mesh import make_host_mesh
    from repro.models import init_lm_params, transformer as T
    from repro.parallel import sharding as sh
    from repro.serve import EngineConfig

    cfg = get_config(arch)
    if reduced:
        cfg = cfg.reduced()
    cfg = dataclasses.replace(cfg, param_dtype="bfloat16",
                              compute_dtype="bfloat16")
    print(f"[config] {cfg.name}: {cfg.n_layers} layers, d_model "
          f"{cfg.d_model}, {cfg.n_heads} heads / {cfg.n_kv_heads} KV heads "
          f"x {cfg.head_dim_}, vocab {cfg.vocab_size}, bf16, tp={tp}")
    mesh = make_host_mesh(model=tp)
    shapes = jax.eval_shape(lambda k: init_lm_params(cfg, k),
                            jax.random.PRNGKey(seed))
    shard = sh.shardings(sh.param_specs(shapes, mesh, sh.serve_rules()),
                         mesh)
    t0 = time.perf_counter()
    params = jax.jit(init_lm_params, static_argnums=0, out_shardings=shard)(
        cfg, jax.random.PRNGKey(seed))
    params = jax.block_until_ready(params)
    print(f"[init] {_tree_bytes(params)} param bytes, sharded on "
          f"{len(mesh.devices.flat)} devices, "
          f"{time.perf_counter() - t0:.3f} s")
    requests = make_requests(cfg.vocab_size, seed, n_requests, prompt_range,
                             new_range, page_tokens=16)
    # fewer slots than requests: the last one (a repeat of the first)
    # is admitted after the first has published its pages
    ecfg = EngineConfig(slots=4, max_len=512, paged=True, page_tokens=16,
                        prefix_cache=True, kernel_impl=impl, tp=tp)
    before = log.snapshot()
    eng, out = serve(params, cfg, ecfg, requests)
    del params
    exe = out.pop("executor")
    comp = log.snapshot()
    print(f"[serve:tp{tp}] kernel_report {out['kernel_report']}")
    print(f"[serve:tp{tp}] compiled_shapes {out['compiled_shapes']}, engine "
          f"steps {out['steps']}, wall {out['wall_s']:.3f} s (compile "
          f"included), backend compile "
          f"{comp['backend_compile_s'] - before['backend_compile_s']:.3f} s")
    print(f"[shard] KV heads {cfg.n_kv_heads} padded with {exe.kv_pad} "
          f"all-zero heads to {exe.cfg.n_kv_heads} (a multiple of tp={tp})")
    pool = {k: v for k, v in eng.state["blocks"][0]["kv"].items()}
    print(f"[shard] per-device KV pool bytes {_per_device_bytes(pool)} "
          f"(total {out['kv_pool_bytes']})")
    print(f"[shard] per-device param bytes {_per_device_bytes(exe.params)} "
          f"(total {out['param_bytes']})")
    pool_dev = _per_device_bytes(pool)
    if len(pool_dev) != tp or max(pool_dev.values()) * tp > \
            out["kv_pool_bytes"] * 1.01:
        raise Failure(f"KV pool is not split across {tp} devices: "
                      f"{pool_dev}")
    par_dev = _per_device_bytes(exe.params)
    if max(par_dev.values()) > 0.5 * out["param_bytes"]:
        raise Failure(f"params are not sharded: {par_dev}")
    check_kernels(out["kernel_report"], ("decode_step", "page_copy"), impl)
    for d in jax.devices()[:tp]:
        mem = d.memory_stats() or {}
        print(f"[memory] device {d.id}: peak_bytes_in_use "
              f"{mem.get('peak_bytes_in_use')} of {mem.get('bytes_limit')}")

    # reference: one full forward pass per request over prompt + its own
    # generated tokens (teacher forced, so every position sees exactly
    # the tokens the engine saw), padded to one length -> one compile
    L = max(len(r.prompt) + r.max_new_tokens for r in requests)
    ref_cfg = dataclasses.replace(exe.cfg, kernel_impl="xla")
    fwd = jax.jit(lambda p, t: T.forward(p, ref_cfg, t)[0])
    t0 = time.perf_counter()
    ref = {"tokens": {}, "emitted": {}}
    with sh.use_mesh(exe.mesh):
        for r in requests:
            gen = out["tokens"][r.uid]
            seq = np.zeros((1, L), np.int32)
            full = np.concatenate([r.prompt, np.asarray(gen[:-1], np.int32)])
            seq[0, :len(full)] = full
            logits = np.asarray(fwd(exe.params, jnp.asarray(seq))[0],
                                np.float32)
            rows = logits[len(r.prompt) - 1:len(full)]
            ref["tokens"][r.uid] = [int(t) for t in rows.argmax(-1)]
            ref["emitted"][r.uid] = [("ref", row) for row in rows]
    print(f"[reference] teacher-forced forward over the engine's sharded "
          f"weights, {len(requests)} requests, "
          f"{time.perf_counter() - t0:.3f} s (compile included)")
    cmp = compare_streams(out, ref, teacher_forced=True)
    report_and_gate(f"tp={tp} {impl} engine vs reference", cmp)
    return cmp


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: GPT-2 XL serving smoke; 4: only the "
                         "tensor-parallel phi3-medium phase")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and the traffic")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {devices[0].platform!r}",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices; JAX found {len(devices)}", file=sys.stderr)
        return 2
    from repro.launch.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    print(f"[device] {devices[0].platform} {devices[0].device_kind} x "
          f"{len(devices)}; jax {jax.__version__}; compile cache {cache}")
    log = CompileLog()
    t0 = time.perf_counter()
    try:
        if args.chips == 1:
            one_chip(args.seed, log)
        else:
            four_chips(args.seed, log)
    except Failure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(f"[total] {time.perf_counter() - t0:.3f} s, {log.snapshot()}")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
