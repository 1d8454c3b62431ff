"""Size a configuration's KV pool from compiles for a described chip.

    JAX_PLATFORMS=cpu python3 bench/sizing.py <config> <pages> [...]

For each pool size, compiles the engine's decode, chunk and page-copy
programs for a described TPU v5e (no chip attached; nothing runs) and
prints each program's argument, output and temporary bytes from the
compiler's memory analysis.  A configuration with ``engine.tp`` above 1
is compiled as ``ShardedExecutor`` compiles it, over a mesh of ``tp``
devices of the described ``v5e:2x2`` host, and the bytes are per device.
The configuration file records the pool it chose and these numbers
under ``sizing``.
"""
import os
import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def described_executor(params, cfg, tp, topo):
    """``(executor class, params)`` for a described chip: the
    executor's placement hands back shapes with their shardings instead
    of arrays (nothing can be put on a described device)."""
    import jax
    from jax.sharding import (AxisType, Mesh, NamedSharding,
                              SingleDeviceSharding)

    from repro.core import prune
    from repro.launch import mesh as mesh_mod
    from repro.parallel import sharding as sh
    from repro.serve import executor as ex

    def place(tree, shardings):
        return jax.tree.map(lambda a, s: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=s), tree, shardings)

    if tp == 1:
        chip = SingleDeviceSharding(topo.devices[0])
        return ex.LocalExecutor, place(params, jax.tree.map(
            lambda _: chip, params))
    # the executor builds its mesh from this process's (CPU) devices and
    # pads KV heads with array ops: here, the described devices' mesh,
    # and the padding on shapes
    mesh = Mesh(np.asarray(topo.devices[:tp]).reshape(1, tp),
                ("data", "model"), axis_types=(AxisType.Auto,) * 2)
    mesh_mod.make_host_mesh = lambda data=None, model=1: mesh
    pad = prune.pad_kv_heads

    def pad_shapes(tree, c, multiple):
        got = {}

        def f(p):
            out, got["cfg"] = pad(p, c, multiple)
            return out
        return jax.eval_shape(f, tree), got["cfg"]
    prune.pad_kv_heads = pad_shapes

    class Described(ex.ShardedExecutor):
        def _place_params(self, tree):
            specs = sh.param_specs(tree, self.mesh, sh.serve_rules())
            return place(tree, jax.tree.map(
                lambda s: NamedSharding(self.mesh, s), specs))

    return Described, params


def main(argv):
    import dataclasses
    import json

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec as P

    from bench import load_family, model as bm
    from repro.kernels.ops import KernelDispatch
    from repro.models import transformer as T
    from repro.parallel import sharding as sh
    from repro.serve import executor as ex

    conf = json.loads((ROOT / "bench" / "configs" / f"{argv[0]}.json")
                      .read_text())
    fam = load_family(ROOT, conf)
    d = fam.dims(conf)
    tp = int(conf["engine"].get("tp", 1))
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    # the chip donates the state into every step; this CPU process
    # would not, and the analysis would count the pool twice
    ex._donation_supported = lambda: True
    cfg = dataclasses.replace(fam.arch_config(conf, "xla"),
                              kernel_impl=KernelDispatch("pallas"))
    shapes = jax.eval_shape(lambda k: fam.build(k, d), jax.random.PRNGKey(0))
    make, params = described_executor(shapes, cfg, tp, topo)
    for pages in map(int, argv[1:]):
        ecfg = dataclasses.replace(bm.engine_config(conf, "xla"),
                                   n_pages=pages)
        exe = make(params, cfg, ecfg)
        state = jax.eval_shape(
            lambda: T.init_decode_state_paged(exe.cfg, ecfg.slots, pages,
                                              ecfg.page_tokens))
        if tp == 1:
            one = jax.tree.leaves(params)[0].sharding
            state = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
                a.shape, a.dtype, sharding=one), state)
        else:
            one = NamedSharding(exe.mesh, P())
            # what ShardedExecutor._place_state records and places
            exe._state_specs = sh.serve_state_specs(state, exe.mesh,
                                                    paged=True)
            state = jax.tree.map(lambda a, s: jax.ShapeDtypeStruct(
                a.shape, a.dtype, sharding=NamedSharding(exe.mesh, s)),
                state, exe._state_specs)
        B, C, Pg = ecfg.slots, ecfg.chunk, ecfg.table_pages

        def s(shape, dtype=jnp.int32):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

        with exe._ctx():
            progs = {
                "decode": exe._decode.lower(
                    exe.params, s((B,)), s((B,), bool), s((B,)),
                    s((B, Pg)), s((B,)), None, None, state),
                "chunk": exe._chunk.lower(
                    exe.params, s((B, C)), s((B,)), s((B,), bool), s((B,)),
                    s((B, Pg)), s((B,)), None, None, state),
                "page_copy": exe._copy.lower(state["blocks"], s((B,)),
                                             s((B,))),
            }
        pool = sum(int(a.size) * a.dtype.itemsize
                   for a in jax.tree.leaves(state["blocks"]))
        out = {"pages": pages, "pool_bytes": pool}
        if tp > 1:
            out.update(devices=tp, pool_bytes_per_device=sum(
                _shard_bytes(a) for a in jax.tree.leaves(state["blocks"])),
                param_bytes_per_device=sum(
                    _shard_bytes(a) for a in jax.tree.leaves(exe.params)))
        for name, low in progs.items():
            m = low.compile().memory_analysis()
            out[name] = {"argument_bytes": m.argument_size_in_bytes,
                         "output_bytes": m.output_size_in_bytes,
                         "alias_bytes": m.alias_size_in_bytes,
                         "temp_bytes": m.temp_size_in_bytes}
        print(json.dumps(out), flush=True)


def _shard_bytes(a) -> int:
    """Bytes of one device's shard of a shape with a sharding."""
    shard = a.sharding.shard_shape(a.shape)
    return int(np.prod(shard, dtype=np.int64)) * a.dtype.itemsize


if __name__ == "__main__":
    main(sys.argv[1:])
