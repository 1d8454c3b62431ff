"""Benchmark driver: one module per paper table/figure.

``PYTHONPATH=src python -m benchmarks.run``                  # all
``PYTHONPATH=src python -m benchmarks.run table1``           # substring
``PYTHONPATH=src python -m benchmarks.run --only serve_bench``  # exact
Each module returns {..., "checks": {name: bool}}; the driver reports
every check and exits non-zero if any reproduced claim fails OR any
module crashes (a raise is recorded as that module's failure, the
remaining modules still run, and the exit code is non-zero).

Perf modules (``*_bench``) additionally get a machine-readable dump
``BENCH_<stem>.json`` (e.g. BENCH_serve.json, BENCH_kernel.json) written
to the REPO ROOT — rows, checks and the module's ``metrics`` dict
(tokens/sec, p50/p95 ITL, TTFT, page-pool utilization, ...).  The root
files are COMMITTED (and also uploaded as CI workflow artifacts), so
the perf trajectory is tracked in-repo across PRs instead of
evaporating with the build log; ``compare.py`` verifies they stay
key-synchronized with ``benchmarks/baselines/``.
"""
from __future__ import annotations

import json
import os
import sys
import time
import traceback
from pathlib import Path

# Anchor BENCH_*.json at the repo root regardless of the invoking CWD:
# "written wherever the runner happened to cd" is how the committed
# perf trajectory ended up empty.  BENCH_OUTPUT_DIR redirects the
# output for runs that must NOT touch the committed trajectory files
# (subprocess tests, scenario-filtered smokes).
REPO_ROOT = Path(__file__).resolve().parent.parent

# serve_bench's tp cells need >= 2 devices, and XLA only honors the
# host-device-count flag before jax first initializes.  Set it HERE,
# before any benchmark module import: when serve_bench runs after a
# module that already imported jax (e.g. kernel_bench in the same
# process), its own import-time guard is too late, the tp > 1 cells
# cannot form a mesh, and (before they raised) the run silently
# dropped their gated baseline keys.
if ("jax" not in sys.modules
        and "xla_force_host_platform_device_count"
        not in os.environ.get("XLA_FLAGS", "")):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=4"
                               ).strip()

MODULES = ("table1_pruning", "table2_peft", "fig2_spectrum",
           "fig3_trainfree", "fig4_projection", "fig56_rank",
           "kernel_bench", "serve_bench")


def _write_bench_json(name: str, out: dict, elapsed_s: float) -> str:
    out_dir = Path(os.environ.get("BENCH_OUTPUT_DIR") or REPO_ROOT)
    path = out_dir / f"BENCH_{name[:-len('_bench')]}.json"
    payload = {
        "module": name,
        "elapsed_s": round(elapsed_s, 2),
        "rows": [list(r) for r in out.get("rows", [])],
        "checks": out.get("checks", {}),
        "metrics": out.get("metrics", {}),
    }
    with open(path, "w") as f:
        json.dump(payload, f, indent=2, sort_keys=True)
        f.write("\n")
    try:
        return str(path.relative_to(Path.cwd()))
    except ValueError:
        return str(path)


def main(argv=None) -> int:
    from repro.launch.compile_cache import enable_compile_cache
    argv = list(argv if argv is not None else sys.argv[1:])
    enable_compile_cache()
    # --only <module>: exact-name filter (repeatable) for local
    # iteration; bare args remain substring filters
    only, subs = [], []
    i = 0
    while i < len(argv):
        if argv[i] == "--only":
            if i + 1 >= len(argv):
                print("--only requires a module name")
                return 2
            only.append(argv[i + 1])
            i += 2
        elif argv[i].startswith("--only="):
            only.append(argv[i].split("=", 1)[1])
            i += 1
        else:
            subs.append(argv[i])
            i += 1
    unknown = [m for m in only if m not in MODULES]
    if unknown:
        print(f"--only: unknown modules {unknown}; known: {MODULES}")
        return 2
    selected = [m for m in MODULES
                if (m in only if only else
                    (not subs or any(a in m for a in subs)))]
    if not selected:
        # a typo'd selector must not report ALL CHECKS PASS (CI runs
        # this driver with explicit module names)
        print(f"no benchmark modules match {subs}; known: {MODULES}")
        return 2
    failures = []
    for name in selected:
        print(f"\n=== {name} " + "=" * (60 - len(name)))
        t0 = time.time()
        # a crashing benchmark is a FAILURE of that module, never a
        # silent pass NOR an abort that hides the remaining modules'
        # results — record it, keep going, exit non-zero at the end
        try:
            mod = __import__(f"benchmarks.{name}", fromlist=["run"])
            out = mod.run(verbose=True)
            checks = out["checks"]
        except Exception as e:  # noqa: BLE001 - the driver must survive
            traceback.print_exc()
            failures.append(f"{name}:raised:{type(e).__name__}")
            print(f"  [FAIL] {name} raised {type(e).__name__}: {e}")
            continue
        dt = time.time() - t0
        for check, ok in checks.items():
            status = "PASS" if ok else "FAIL"
            print(f"  [{status}] {check}")
            if not ok:
                failures.append(f"{name}:{check}")
        if name.endswith("_bench"):
            print(f"  wrote {_write_bench_json(name, out, dt)}")
        print(f"  ({dt:.1f}s)")
    print("\n" + ("ALL CHECKS PASS" if not failures
                  else f"FAILURES: {failures}"))
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
