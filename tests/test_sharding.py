"""Distribution: spec validity, multi-device pjit equivalence (subprocess
with forced host devices), elastic restore, pipeline, compression."""
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, ASSIGNED_ARCHS
from repro.launch.mesh import make_host_mesh
from repro.models import init_lm_params, init_decode_state
from repro.parallel import sharding as sh

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run_subprocess(code: str, devices: int = 8) -> str:
    """Run code in a fresh process with N forced host devices."""
    prog = (f"import os\n"
            f"os.environ['XLA_FLAGS'] = "
            f"'--xla_force_host_platform_device_count={devices}'\n"
            + textwrap.dedent(code))
    env = dict(os.environ, PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", prog], env=env,
                         capture_output=True, text=True, timeout=1200)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


# ---------------------------------------------------------------------------
# spec validity for every arch on production-shaped meshes (no devices
# needed: divisibility logic is pure)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", ASSIGNED_ARCHS)
def test_param_specs_divisible(name):
    cfg = get_config(name).reduced()
    params = init_lm_params(cfg, jax.random.PRNGKey(0))
    mesh = make_host_mesh()          # (1, 1) on CPU
    specs = sh.param_specs(params, mesh)
    # every spec entry must divide its dim (mesh extents are 1 -> trivial
    # here; the real check runs inside the dry-run on 512 devices).
    for (path, leaf), (_, spec) in zip(
            jax.tree_util.tree_flatten_with_path(params)[0],
            jax.tree_util.tree_flatten_with_path(
                specs, is_leaf=lambda x: isinstance(
                    x, jax.sharding.PartitionSpec))[0]):
        assert len(spec) <= leaf.ndim, (path, spec, leaf.shape)


def test_rules_drop_nondivisible():
    rules = sh.ShardingRules()

    class FakeMesh:
        shape = {"data": 16, "model": 16}
    spec = rules.spec((sh.HEADS, None), (40, 128), FakeMesh())
    assert spec[0] is None               # 40 % 16 != 0 -> replicated
    spec = rules.spec((sh.HEADS, None), (32, 128), FakeMesh())
    assert spec[0] == "model"


def test_decode_state_specs_structure():
    cfg = get_config("jamba-v0.1-52b").reduced()
    st = init_decode_state(cfg, 4, 32)
    mesh = make_host_mesh()
    specs = sh.decode_state_specs(st, mesh)
    flat = jax.tree_util.tree_flatten_with_path(
        specs, is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec))[0]
    assert len(flat) == len(jax.tree_util.tree_leaves(st))


def test_make_host_mesh_rejects_nondivisible_model():
    """A tp degree that does not divide the device count must die with
    a CLEAR ValueError naming both numbers — not jax.make_mesh's
    cryptic reshape failure."""
    n = jax.device_count()
    bad = n + 1                          # never divides n (n >= 1)
    with pytest.raises(ValueError, match=f"model={bad} does not divide"):
        make_host_mesh(model=bad)
    with pytest.raises(ValueError, match="must be >= 1"):
        make_host_mesh(model=0)
    with pytest.raises(ValueError, match="devices"):
        make_host_mesh(data=n + 1, model=1)
    mesh = make_host_mesh(model=1)       # the happy path still works
    assert mesh.shape == {"data": n, "model": 1}


# ---------------------------------------------------------------------------
# multi-device equivalence: sharded pjit train step == single-device step
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_pjit_train_step_matches_single_device():
    out = _run_subprocess("""
        import dataclasses, json
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_config
        from repro.models import init_lm_params
        from repro.launch.mesh import make_mesh
        from repro.optim import AdamWConfig
        from repro.parallel import sharding as sh
        from repro.train.step import TrainConfig, make_train_step, make_opt_state

        cfg = get_config('musicgen-large').reduced()
        key = jax.random.PRNGKey(0)
        params = init_lm_params(cfg, key)
        toks = jax.random.randint(key, (8, 16), 0, cfg.vocab_size)
        batch = {'tokens': toks, 'labels': toks}
        tcfg = TrainConfig(optimizer=AdamWConfig(lr=1e-3), warmup_steps=1,
                           total_steps=10, remat=True)

        def run(mesh):
            step, _ = make_train_step(cfg, tcfg, mesh)
            opt = make_opt_state(params)
            pspec = sh.param_specs(params, mesh)
            p_sh = sh.shardings(pspec, mesh)
            o_sh = {'m': p_sh, 'v': p_sh,
                    'step': jax.sharding.NamedSharding(
                        mesh, jax.sharding.PartitionSpec())}
            d_sh = jax.sharding.NamedSharding(mesh, sh.data_specs(mesh))
            with sh.use_mesh(mesh):
                j = jax.jit(step, in_shardings=(p_sh, o_sh,
                                                {'tokens': d_sh, 'labels': d_sh}),
                            out_shardings=None)
                p2, o2, m = j(params, opt, batch)
            return float(m['loss']), p2

        loss_1, p1 = run(make_mesh((1, 1), ('data', 'model')))
        loss_8, p8 = run(make_mesh((4, 2), ('data', 'model')))
        # host-side compare: the two trees live on different meshes
        diff = max(float(np.max(np.abs(
            np.asarray(a, np.float32) - np.asarray(b, np.float32))))
            for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p8)))
        print(json.dumps({'loss_1': loss_1, 'loss_8': loss_8, 'diff': diff}))
    """)
    res = json.loads(out.strip().splitlines()[-1])
    assert abs(res["loss_1"] - res["loss_8"]) < 1e-3, res
    assert res["diff"] < 5e-2, res


@pytest.mark.slow
def test_moe_sharded_dispatch_matches_local():
    """Per-shard-capacity MoE on a 4-way data mesh == the local path when
    dropless (capacity_factor=0 -> nothing dropped either way)."""
    out = _run_subprocess("""
        import dataclasses, json
        import jax, jax.numpy as jnp
        from repro.configs import get_config
        from repro.models import init_lm_params, forward
        from repro.launch.mesh import make_mesh
        from repro.parallel import sharding as sh

        cfg = get_config('granite-moe-1b-a400m').reduced()
        cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
            cfg.moe, capacity_factor=0.0))
        key = jax.random.PRNGKey(0)
        params = init_lm_params(cfg, key)
        toks = jax.random.randint(key, (8, 16), 0, cfg.vocab_size)
        base, _ = forward(params, cfg, toks)      # no mesh: local path
        mesh = make_mesh((4, 2), ('data', 'model'))
        with sh.use_mesh(mesh):
            out, _ = jax.jit(lambda p, t: forward(p, cfg, t))(params, toks)
        err = float(jnp.max(jnp.abs(out - base)))
        print(json.dumps({'err': err}))
    """)
    res = json.loads(out.strip().splitlines()[-1])
    assert res["err"] < 1e-3, res


@pytest.mark.slow
def test_elastic_checkpoint_reshard():
    """Save under a (4,2) mesh, restore onto (2,2) and single-device."""
    out = _run_subprocess("""
        import json, tempfile
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_config
        from repro.models import init_lm_params
        from repro.launch.mesh import make_mesh
        from repro.parallel import sharding as sh
        from repro.train.checkpoint import CheckpointManager

        cfg = get_config('stablelm-3b').reduced()
        params = init_lm_params(cfg, jax.random.PRNGKey(0))
        mesh_a = make_mesh((4, 2), ('data', 'model'))
        sh_a = sh.shardings(sh.param_specs(params, mesh_a), mesh_a)
        sharded = jax.tree.map(jax.device_put, params, sh_a)
        d = tempfile.mkdtemp()
        cm = CheckpointManager(d, async_write=False)
        cm.save(1, sharded)
        mesh_b = make_mesh((2, 2), ('data', 'model'))
        sh_b = sh.shardings(sh.param_specs(params, mesh_b), mesh_b)
        restored, _ = cm.restore(1, params, sh_b)
        diff = max(float(jnp.max(jnp.abs(
            a.astype(jnp.float32) - b.astype(jnp.float32))))
            for a, b in zip(jax.tree.leaves(params),
                            jax.tree.leaves(restored)))
        print(json.dumps({'diff': diff}))
    """)
    res = json.loads(out.strip().splitlines()[-1])
    assert res["diff"] == 0.0, res


@pytest.mark.slow
def test_pipeline_matches_sequential():
    out = _run_subprocess("""
        import json
        import jax, jax.numpy as jnp
        from repro.launch.mesh import make_mesh
        from repro.parallel.pipeline import pipeline_apply

        p_stages = 4
        D = 16
        key = jax.random.PRNGKey(0)
        Ws = jax.random.normal(key, (p_stages, D, D)) * 0.3
        x = jax.random.normal(jax.random.PRNGKey(1), (16, D))

        def stage(w, h):
            return jnp.tanh(h @ w['w'])

        params = {'w': Ws}
        # sequential reference
        ref = x
        for i in range(p_stages):
            ref = stage({'w': Ws[i]}, ref)
        mesh = make_mesh((p_stages,), ('pipe',))
        out = pipeline_apply(params, x, mesh, stage, n_microbatches=8)
        err = float(jnp.max(jnp.abs(out - ref)))
        print(json.dumps({'err': err}))
    """, devices=4)
    res = json.loads(out.strip().splitlines()[-1])
    assert res["err"] < 1e-5, res


@pytest.mark.slow
def test_grad_compression_cross_pod():
    out = _run_subprocess("""
        import json
        import jax, jax.numpy as jnp
        from repro.launch.mesh import make_mesh
        from repro.parallel.compress import (compress_cross_pod,
                                             compress_cross_pod_ef,
                                             init_residual)
        mesh = make_mesh((4, 2), ('pod', 'data'))
        g = {'w': jnp.linspace(-1, 1, 64).reshape(8, 8)}
        with mesh:
            avg = jax.jit(lambda t: compress_cross_pod(t, mesh))(g)
        # identical replicas -> average == input (up to int8 quantization)
        err = float(jnp.max(jnp.abs(avg['w'] - g['w'])))
        res = init_residual(g)
        with mesh:
            avg2, r2 = jax.jit(
                lambda t, r: compress_cross_pod_ef(t, r, mesh))(g, res)
        err2 = float(jnp.max(jnp.abs(avg2['w'] - g['w'])))
        # error feedback captures exactly what quantization lost
        recon = float(jnp.max(jnp.abs(avg2['w'] + r2['w'] - g['w'])))
        print(json.dumps({'err': err, 'err2': err2, 'recon': recon}))
    """)
    res = json.loads(out.strip().splitlines()[-1])
    assert res["err"] < 1e-2, res      # int8 quantization noise
    assert res["recon"] < 1e-5, res    # EF residual is exact
