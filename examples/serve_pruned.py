"""Serving example: chunked-prefill continuous batching over a
CLOVER-pruned model.

Builds a reduced model, CLOVER-prunes 50% of every head (KV cache
halves), then serves a mixed batch of requests with different prompt
lengths and arrival times.  Prompts are consumed in fixed-size chunks
interleaved with decoding, so the whole mixed-length batch compiles
exactly two step shapes; each stream is verified against its isolated
greedy reference.  The same trace is then replayed against an
UNDERSIZED page pool (admission gated on free pages, preemption on
exhaustion) and must produce identical streams — and,
with ``--spec-k`` > 0, replayed once more with SELF-SPECULATIVE
decoding (a rank-sliced draft of the same weights proposes tokens, one
multi-token verify step commits a greedy prefix; DESIGN.md §8), again
token-identical.  Finally a shared-system-prompt batch runs twice on a
PREFIX-CACHED paged engine (DESIGN.md §9): the warm replay maps the
cached prompt pages read-only, skips their prefill chunks and still
matches the cold streams exactly.  With ``--host-pages N`` the shared
batch also runs against a HIERARCHICAL KV engine (DESIGN.md §12): a
host-RAM spill tier under the trie catches the pages cache pressure
evicts, and the replay restores the prefix from host RAM through one
fixed-width scatter instead of re-prefilling — the demo prints the
spill/restore counters and host hit rate from ``Engine.stats()``.

With ``--tp N`` the paged trace is replayed once more through the
rank-balanced ShardedExecutor (DESIGN.md §10): params and KV page
pools shard along heads over a ("data", "model") host mesh, the
head -> shard assignment planned so every shard carries ~equal pruned
FLOPs/bytes, and the streams must again be token-identical.  The
replay prints ``Engine.exe.kernel_report()`` — which kernel impl each
compiled entry (decode step, prefill chunk, draft/verify, page copy)
ACTUALLY used, e.g. ``interpret+shard_map(model=2)`` when the Pallas
hot path compiled per shard; ``--kernel-impl`` overrides the dispatch
of the tensor-parallel and rank-budget replays (``ref | xla | pallas |
interpret``; ``pallas`` needs a TPU, ``interpret`` runs the same
kernels on CPU).

With ``--rank-budget F`` the demo plans a NON-UNIFORM prune
(DESIGN.md §14): ``plan_rank_budget`` water-fills ``F`` of the model's
total rank capacity across layers/heads by singular-value energy,
prints every layer's kept per-head ranks and the analytic pool bytes
(``rank_pool_bytes``: kept vs max-width-allocated), then serves the
plan — ragged ranks as zero-padding plus the decode kernels' per-head
rank clamp — and verifies each stream against its greedy reference.

With ``--adapters N`` the demo also serves a MULTI-TENANT batch
(DESIGN.md §13): one base model plus ``N`` registered SV adapters —
per-tenant multiplicative scalings of the CLOVER singular values that
the attention einsums apply elementwise, so tenants share every weight
and compiled shape.  Requests carry ``adapter_id``; each stream is
verified against a single-tenant replay on the model with that
adapter folded into the diagonals, and the demo prints the per-tenant
token/completion counters from ``Engine.stats()``.

The final section demonstrates GRACEFUL DEGRADATION under overload
(DESIGN.md §11): a two-priority burst against a deliberately small
engine, low-priority requests carrying ``--deadline-steps``, one
request cancelled mid-flight, and — with ``--chaos-seed`` — a
deterministic fault schedule injected at the host boundaries (allocator
exhaustion, step failures, NaN logits, page-copy faults) that the
engine must absorb via bounded retry / quarantine / shedding while
every surviving stream stays token-exact.  It ends by printing the
``engine.stats()`` counter + per-priority-class latency table.

Run:  PYTHONPATH=src python examples/serve_pruned.py
      PYTHONPATH=src python examples/serve_pruned.py --spec-k 4
      PYTHONPATH=src python examples/serve_pruned.py --adapters 2
      PYTHONPATH=src python examples/serve_pruned.py \
          --chaos-seed 7 --deadline-steps 20
      XLA_FLAGS=--xla_force_host_platform_device_count=4 \
          PYTHONPATH=src python examples/serve_pruned.py \
          --tp 2 --kernel-impl interpret
"""
import argparse
import dataclasses
import time

import jax
import numpy as np

from repro.configs import get_config
from repro.core import (AdapterRegistry, apply_rank_budget,
                        clover_decompose, clover_prune, plan_rank_budget)
from repro.launch.compile_cache import enable_compile_cache
from repro.models import init_lm_params
from repro.serve import (Engine, EngineConfig, FaultPlan, Request,
                         greedy_reference, rank_pool_bytes)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--spec-k", type=int, default=2,
                    help="draft tokens per speculative round (0 = off)")
    ap.add_argument("--draft-rank-ratio", type=float, default=0.5,
                    help="fraction of every head's current rank the "
                         "draft slices off (0.0 = draft is the exact "
                         "model)")
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor-parallel degree for the sharded "
                         "replay (must divide jax.device_count(); on "
                         "CPU export XLA_FLAGS=--xla_force_host_"
                         "platform_device_count=N first)")
    ap.add_argument("--kernel-impl", default="",
                    choices=("", "ref", "xla", "pallas", "interpret"),
                    help="kernel dispatch override for the sharded "
                         "and rank-budget replays (default: inherit "
                         "the arch config; 'pallas' needs a TPU, "
                         "'interpret' runs the Pallas kernels on CPU "
                         "and exercises the per-head rank clamp)")
    ap.add_argument("--host-pages", type=int, default=8,
                    help="host-RAM spill-tier capacity (pages) for the "
                         "hierarchical-KV demo (0 = skip it)")
    ap.add_argument("--adapters", type=int, default=2,
                    help="number of per-tenant SV adapters for the "
                         "multi-tenant demo (0 = skip it; id 0 is "
                         "always the identity/base tenant)")
    ap.add_argument("--rank-budget", type=float, default=0.5,
                    help="fraction of TOTAL rank capacity for the "
                         "spectrum-planned non-uniform serving demo "
                         "(DESIGN.md §14; 0 = skip it)")
    ap.add_argument("--chaos-seed", type=int, default=None,
                    help="inject a deterministic FaultPlan with this "
                         "seed into the overload demo (omit = "
                         "fault-free; same seed = same faults)")
    ap.add_argument("--deadline-steps", type=int, default=24,
                    help="deadline (engine steps) on the low-priority "
                         "half of the overload demo: queued requests "
                         "that provably cannot meet it are shed when "
                         "higher-priority work is pending; running "
                         "ones time out with a partial stream")
    args = ap.parse_args()
    enable_compile_cache()
    cfg = get_config("musicgen-large").reduced()
    params = init_lm_params(cfg, jax.random.PRNGKey(0))
    dparams, dcfg, extras = clover_decompose(params, cfg, peft=False)
    pparams, pcfg = clover_prune(dparams, dcfg, qk_ratio=0.5, vo_ratio=0.5)
    print(f"serving {pcfg.name}: head_dim {cfg.head_dim_} -> "
          f"qk_rank {pcfg.clover.qk_rank}, vo_rank {pcfg.clover.vo_rank}")

    eng = Engine(pparams, pcfg, EngineConfig(slots=4, max_len=96,
                                             prefill_chunk=8))
    rng = np.random.default_rng(0)
    reqs = [Request(uid=i,
                    prompt=rng.integers(0, cfg.vocab_size,
                                        int(rng.integers(3, 12))).astype(
                                            np.int32),
                    max_new_tokens=8)
            for i in range(10)]
    t0 = time.time()
    eng.run(reqs)
    dt = time.time() - t0
    n_tok = sum(len(r.generated) for r in reqs)
    print(f"served {len(reqs)} requests / {n_tok} tokens in {dt:.1f}s "
          f"({eng.compiled_shapes()} compiled step shapes)")

    # verify stream 0 against its isolated reference
    r = reqs[0]
    ref = greedy_reference(pparams, pcfg, r.prompt, r.max_new_tokens)
    print(f"request 0: engine={r.generated}")
    print(f"           ref   ={ref}  match={r.generated == ref}")

    # replay on an undersized pool -> page-gated admission +
    # preemption, identical streams
    ep = Engine(pparams, pcfg, EngineConfig(slots=4, max_len=96,
                                            prefill_chunk=8,
                                            page_tokens=8, n_pages=8))
    reqs_p = [Request(uid=r.uid, prompt=r.prompt,
                      max_new_tokens=r.max_new_tokens) for r in reqs]
    ep.run(reqs_p)
    match = all(a.generated == b.generated for a, b in zip(reqs, reqs_p))
    print(f"undersized-pool replay: match={match} "
          f"({ep.compiled_shapes()} compiled step shapes, "
          f"{ep.sched.preemptions} preemptions, "
          f"peak page util {ep.peak_page_util:.0%})")

    # spectrum-planned rank budget (DESIGN.md §14): water-fill ONE
    # global rank budget across layers/heads by singular-value energy,
    # then serve the non-uniform plan — per-head ragged ranks ride as
    # zero-padding plus the decode kernels' per-head rank clamp, so
    # every stream still matches its greedy reference at ONE compiled
    # shape per plan
    if args.rank_budget > 0:
        plan = plan_rank_budget(extras, dcfg, budget=args.rank_budget)
        bparams, bcfg = apply_rank_budget(dparams, dcfg, plan)
        print(f"rank budget {args.rank_budget:.0%}: kept "
              f"{plan.total_rank} of {plan.budget} requested ranks, "
              f"widths qk={plan.qk_width} vo={plan.vo_width}")
        for j in range(len(bcfg.pattern)):
            if not plan.qk_ranks[j]:
                continue
            qk_j, vo_j = plan.layer_ranks(j)
            for b in range(qk_j.shape[0]):
                print(f"  layer {j}.{b}: qk {qk_j[b].tolist()} "
                      f"vo {vo_j[b].tolist()}")
        pb = rank_pool_bytes(plan, page_tokens=8, n_pages=8)
        print(f"  pool bytes: kept {pb['kept']} / allocated "
              f"{pb['allocated']} "
              f"({pb['kept'] / pb['allocated']:.0%} of max-width pool)")
        eb = Engine(bparams, bcfg,
                    EngineConfig(slots=4, max_len=96, prefill_chunk=8,
                                 page_tokens=8,
                                 kernel_impl=args.kernel_impl,
                                 rank_budget=plan))
        reqs_b = [Request(uid=r.uid, prompt=r.prompt,
                          max_new_tokens=r.max_new_tokens)
                  for r in reqs[:4]]
        eb.run(reqs_b)
        match = all(
            r.generated == greedy_reference(bparams, bcfg, r.prompt,
                                            r.max_new_tokens)
            for r in reqs_b)
        print(f"  budget-planned replay: match={match} "
              f"({eb.compiled_shapes()} compiled step shapes)")

    # replay once more with self-speculative decoding: the rank-sliced
    # draft of the SAME weights proposes spec_k tokens per decode step,
    # one (slots, k+1) verify step commits a greedy prefix — identical
    # streams, more tokens per full-model step (DESIGN.md §8)
    if args.spec_k > 0:
        es = Engine(pparams, pcfg,
                    EngineConfig(slots=4, max_len=96, prefill_chunk=8,
                                 spec_k=args.spec_k,
                                 draft_rank_ratio=args.draft_rank_ratio))
        reqs_s = [Request(uid=r.uid, prompt=r.prompt,
                          max_new_tokens=r.max_new_tokens) for r in reqs]
        es.run(reqs_s)
        match = all(a.generated == b.generated
                    for a, b in zip(reqs, reqs_s))
        print(f"speculative replay (k={args.spec_k}, draft ratio "
              f"{args.draft_rank_ratio}): match={match}, "
              f"{es.accepted_per_round:.2f} accepted tokens/step "
              f"(hist {dict(sorted(es.accept_hist.items()))}, "
              f"{es.compiled_shapes()} compiled step shapes)")

    # rank-balanced tensor-parallel replay (DESIGN.md §10): the SAME
    # paged trace through the ShardedExecutor — params and page pools
    # sharded along heads, streams still token-identical, and the page
    # pool's bytes split ~evenly across shards by the rank-balanced
    # head partition
    if args.tp > 1:
        if jax.device_count() % args.tp != 0:
            print(f"--tp {args.tp}: skipped — needs a device count "
                  f"divisible by {args.tp} (have {jax.device_count()}; "
                  "export XLA_FLAGS=--xla_force_host_platform_device_"
                  "count=4)")
        else:
            et = Engine(pparams, pcfg,
                        dataclasses.replace(
                            EngineConfig(slots=4, max_len=96,
                                         prefill_chunk=8,
                                         page_tokens=8, n_pages=8),
                            tp=args.tp,
                            kernel_impl=args.kernel_impl))
            reqs_t = [Request(uid=r.uid, prompt=r.prompt,
                              max_new_tokens=r.max_new_tokens)
                      for r in reqs]
            et.run(reqs_t)
            match = all(a.generated == b.generated
                        for a, b in zip(reqs, reqs_t))
            plan = et.exe.plan
            print(f"tensor-parallel replay (tp={args.tp}): match={match} "
                  f"({et.compiled_shapes()} compiled step shapes, "
                  f"{et.sched.preemptions} preemptions)")
            print("  kernel dispatch per compiled entry:")
            for entry, impl in et.exe.kernel_report().items():
                print(f"    {entry:>13}: {impl}")
            used = et.alloc.used_pages()
            for s, frac in enumerate(et.exe.shard_load_fractions()):
                heads = plan.kv_assign[s] if plan is not None else "all"
                print(f"  shard {s}: kv heads {heads} — "
                      f"{et.peak_page_util:.0%} of pool pages at peak, "
                      f"{frac:.0%} of pooled KV bytes "
                      f"({used} pages mapped now)")

    # prefix caching: a batch sharing one system prompt, served twice
    # on the same engine — the warm pass hits the trie, skips the
    # shared prefill chunks, and must match the cold pass exactly
    epc = Engine(pparams, pcfg,
                 EngineConfig(slots=4, max_len=96, prefill_chunk=8,
                              page_tokens=8,
                              prefix_cache=True))
    sys_prompt = rng.integers(0, cfg.vocab_size, 32).astype(np.int32)
    shared = [np.concatenate(
        [sys_prompt, rng.integers(0, cfg.vocab_size, 4).astype(np.int32)])
        for _ in range(6)]
    cold = [Request(uid=i, prompt=p, max_new_tokens=8)
            for i, p in enumerate(shared)]
    epc.run(cold)
    warm = [Request(uid=10 + i, prompt=p, max_new_tokens=8)
            for i, p in enumerate(shared)]
    epc.run(warm)
    match = all(a.generated == b.generated for a, b in zip(cold, warm))
    hit = sum(r.cached_tokens for r in warm)
    print(f"prefix-cache warm replay: match={match}, "
          f"{hit} prompt tokens served from shared pages "
          f"({epc.sched.prefix_hits} hits, "
          f"{len(epc.prefix)} trie nodes, "
          f"{epc.compiled_shapes()} compiled step shapes)")

    # hierarchical KV (DESIGN.md §12): the same shared batch with a
    # host-RAM spill tier under the trie.  Evicting the pool (standing
    # in for cache pressure) spills the published prefix host-side;
    # the replay restores it through one host->device scatter instead
    # of re-prefilling — streams identical, stats() shows the tier
    if args.host_pages > 0:
        eh = Engine(pparams, pcfg,
                    EngineConfig(slots=4, max_len=96, prefill_chunk=8,
                                 page_tokens=8,
                                 prefix_cache=True,
                                 host_pages=args.host_pages))
        cold_h = [Request(uid=i, prompt=p, max_new_tokens=8)
                  for i, p in enumerate(shared)]
        eh.run(cold_h)
        evicted = eh.prefix.evict(eh.alloc.n_pages)
        warm_h = [Request(uid=10 + i, prompt=p, max_new_tokens=8)
                  for i, p in enumerate(shared)]
        eh.run(warm_h)
        match = all(a.generated == b.generated
                    for a, b in zip(cold_h, warm_h))
        st = eh.stats()
        print(f"hierarchical KV (--host-pages {args.host_pages}): "
              f"match={match}, {evicted} pages spilled on eviction, "
              f"{st['host_restores']} restored from host RAM "
              f"(spills={st['host_spills']}, "
              f"hit rate {st['host_hit_rate']:.0%}, "
              f"{st['host_pages_used']} host slots held)")

    # multi-tenant SV adapters (DESIGN.md §13): one base model, N
    # tenants as diagonal scalings of the CLOVER singular values.  The
    # mixed batch runs on a prefix-cached engine (per-tenant trie
    # partition); every stream must equal the single-tenant replay on
    # the model with that tenant's adapter folded into the weights.
    if args.adapters > 0:
        dp2, dcfg2, _ = clover_decompose(params, cfg, peft=True)
        reg = AdapterRegistry(dp2)
        import jax.numpy as jnp
        for a in range(1, args.adapters):
            reg.register(tuple(
                {k: jnp.asarray(rng.uniform(0.8, 1.25, np.shape(v)),
                                jnp.float32) for k, v in entry.items()}
                for entry in reg.get(0)))
        ea = Engine(dp2, dcfg2,
                    EngineConfig(slots=4, max_len=96, prefill_chunk=8,
                                 page_tokens=8,
                                 prefix_cache=True),
                    adapters=reg)
        sys_a = rng.integers(0, cfg.vocab_size, 16).astype(np.int32)
        reqs_a = [Request(uid=300 + i,
                          prompt=np.concatenate(
                              [sys_a, rng.integers(0, cfg.vocab_size, 3)
                               .astype(np.int32)]),
                          max_new_tokens=6, adapter_id=i % len(reg))
                  for i in range(4)]
        ea.run(reqs_a)
        match = all(
            r.generated == greedy_reference(
                reg.folded(dp2, r.adapter_id) if r.adapter_id else dp2,
                dcfg2, r.prompt, r.max_new_tokens)
            for r in reqs_a)
        st = ea.stats()
        print(f"multi-tenant replay ({len(reg)} adapters, shared "
              f"weights): match={match} "
              f"({ea.compiled_shapes()} compiled step shapes)")
        print(f"  per-tenant tokens {st['adapter_tokens']}, "
              f"completions {st['adapter_done']}")

    # overload + graceful degradation (DESIGN.md §11): a two-priority
    # burst against a deliberately small engine.  Lows carry
    # --deadline-steps; one low is cancelled mid-decode; --chaos-seed
    # adds a deterministic fault schedule at the host boundaries.
    # Whatever gets shed / times out / is cancelled must leave the
    # allocator exactly as if it never ran — the surviving streams
    # stay token-exact (the chaos soak and serve_bench scenario 6
    # gate this; here we just watch it degrade gracefully).
    faults = (FaultPlan.chaos(seed=args.chaos_seed, intensity=0.05)
              if args.chaos_seed is not None else None)
    eo = Engine(pparams, pcfg,
                EngineConfig(slots=2, max_len=96, prefill_chunk=8,
                             page_tokens=8, n_pages=8,
                             step_retries=1, quarantine_steps=2,
                             watchdog_steps=32),
                faults=faults)
    mk = rng.integers  # overload trace: 6 lows burst, 3 highs overtake
    lows = [Request(uid=100 + i,
                    prompt=mk(0, cfg.vocab_size,
                              int(mk(4, 12))).astype(np.int32),
                    max_new_tokens=8, priority=0,
                    deadline_steps=args.deadline_steps)
            for i in range(6)]
    highs = [Request(uid=200 + i,
                     prompt=mk(0, cfg.vocab_size,
                               int(mk(4, 12))).astype(np.int32),
                     max_new_tokens=8, priority=1)
             for i in range(3)]
    for r in lows:
        eo.submit(r)
    step = 0
    while eo.sched.busy and step < 500:
        if step == 2:                 # high wave jumps the low queue
            for r in highs:
                eo.submit(r)
        if step == 4:                 # client walks away mid-decode
            eo.cancel(lows[1].uid)
        eo.step()
        step += 1
    chaos = (f"chaos seed {args.chaos_seed}" if faults is not None
             else "fault-free")
    print(f"overload demo ({chaos}, deadline {args.deadline_steps} "
          f"steps): drained in {step} steps")
    for r in lows + highs:
        print(f"  uid {r.uid} prio {r.priority}: {r.status:>9} "
              f"({len(r.generated)}/{r.max_new_tokens} tokens)")
    st = eo.stats()
    print("  counters: " + ", ".join(
        f"{k}={v}" for k, v in sorted(st["counters"].items())))
    if faults is not None:
        print(f"  faults injected: {faults.total_injected} "
              f"(sites {dict(faults.injected)})")
    hdr = (f"  {'class':>5} {'n':>3} {'ttft_p50':>9} {'ttft_p95':>9} "
           f"{'itl_p50':>8} {'itl_p95':>8}   (engine steps)")
    print(hdr)
    for prio, row in sorted(st["classes"].items()):
        print(f"  {prio:>5} {row.get('n_ttft_steps', 0):>3} "
              f"{row.get('ttft_steps_p50', float('nan')):>9.1f} "
              f"{row.get('ttft_steps_p95', float('nan')):>9.1f} "
              f"{row.get('itl_steps_p50', float('nan')):>8.1f} "
              f"{row.get('itl_steps_p95', float('nan')):>8.1f}")
    print(f"  pool after drain: {eo.alloc.free_pages}/"
          f"{eo.alloc.n_pages} pages free")


if __name__ == "__main__":
    main()
