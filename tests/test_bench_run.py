"""benchmarks/run.py driver: the in-process module-chaining bug.

serve_bench's tp > 1 cells need >= 2 devices, and XLA only honors
``--xla_force_host_platform_device_count`` before jax first
initializes.  When benchmarks.run chained serve_bench after another
module that imported jax (kernel_bench), serve_bench's own import-time
guard came too late: the tp cells could not form a mesh and were
silently SKIPPED, dropping their gated baseline keys while the run
still reported ALL CHECKS PASS.  Two fixes, both pinned here:

  * the driver itself sets the flag before ANY benchmark module import
    (``benchmarks/run.py``), so chained runs see 4 host devices;
  * serve_bench now RAISES when a requested tp degree cannot form a
    mesh, so a future regression fails loudly instead of passing with
    a hole in the baseline coverage.
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.join(os.path.dirname(__file__), os.pardir, "src")
ROOT = os.path.join(os.path.dirname(__file__), os.pardir)


def _env(**extra):
    env = dict(os.environ, PYTHONPATH=os.pathsep.join((SRC, ROOT)))
    env.pop("XLA_FLAGS", None)      # the driver must not need outside help
    env.update(extra)
    return env


def test_run_driver_forces_host_devices_before_jax():
    """Importing benchmarks.run first must make any later jax import
    (kernel_bench's is the real case) see the forced host devices."""
    prog = textwrap.dedent("""
        import benchmarks.run          # must set XLA_FLAGS itself
        import jax                     # what kernel_bench does next
        assert jax.device_count() >= 4, jax.device_count()
        print("DEVICES_OK")
    """)
    res = subprocess.run([sys.executable, "-c", prog], env=_env(),
                         capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, res.stderr[-3000:]
    assert "DEVICES_OK" in res.stdout


def test_serve_bench_raises_when_tp_mesh_impossible(tmp_path):
    """A tp cell that cannot form its mesh must RAISE, never skip:
    jax is pinned to one device BEFORE serve_bench imports (exactly
    the chained-module failure mode), so the first tp=2 cell of the
    adapter scenario must die with the mesh error."""
    prog = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = ""
        os.environ["SERVE_BENCH_SCENARIO"] = "adapter"
        import jax                     # too late for serve_bench's guard
        assert jax.device_count() == 1, jax.device_count()
        import benchmarks.serve_bench as sb
        # shrink the trace: this test is about the guard, not the gates
        sb.ADAPTER_TENANTS = 1
        sb.ADAPTER_WAVES = 1
        sb.ADAPTER_MAX_NEW = 2
        try:
            sb.run(verbose=False)
        except RuntimeError as e:
            assert "cannot form" in str(e), e
            print("RAISED_OK")
        else:
            raise SystemExit("tp cell silently skipped")
    """)
    res = subprocess.run([sys.executable, "-c", prog], env=_env(),
                         cwd=tmp_path, capture_output=True, text=True,
                         timeout=1200)
    assert res.returncode == 0, res.stderr[-3000:]
    assert "RAISED_OK" in res.stdout


@pytest.mark.slow
def test_chained_modules_keep_tp_keys(tmp_path):
    """The real regression: kernel_bench then serve_bench in ONE driver
    process must still produce the tp2 baseline keys (scenario filter
    keeps the runtime bounded; the adapter scenario has tp cells).
    BENCH_OUTPUT_DIR keeps this run off the committed repo-root
    trajectory files."""
    res = subprocess.run(
        [sys.executable, "-m", "benchmarks.run",
         "--only", "kernel_bench", "--only", "serve_bench"],
        env=_env(SERVE_BENCH_SCENARIO="adapter",
                 BENCH_OUTPUT_DIR=str(tmp_path),
                 # benchmarks.run turns the compile cache on; keep it
                 # off the checkout in a test
                 JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache")),
        cwd=tmp_path, capture_output=True, text=True, timeout=1200)
    assert res.returncode == 0, (res.stdout[-2000:], res.stderr[-2000:])
    data = json.loads((tmp_path / "BENCH_serve.json").read_text())
    tp2 = {r[0] for r in data["rows"]
           if r[0].endswith("_tp2") and r[1] == "tokens_per_step"}
    assert tp2, "tp2 cells silently dropped from the chained run"
