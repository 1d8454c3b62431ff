"""Size a configuration's KV pool from compiles for a described chip.

    JAX_PLATFORMS=cpu python3 bench/sizing.py <config> <pages> [...]

For each pool size, compiles the engine's decode, chunk and page-copy
programs for one described TPU v5e (no chip attached; nothing runs) and
prints each program's argument, output and temporary bytes from the
compiler's memory analysis.  The configuration file records the pool it
chose and these numbers under ``sizing``.
"""
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main(argv):
    import dataclasses
    import json

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from bench import model as bm
    from repro.kernels.ops import KernelDispatch
    from repro.models import transformer as T
    from repro.serve import executor as ex

    conf = json.loads((ROOT / "bench" / "configs" / f"{argv[0]}.json")
                      .read_text())
    d = bm.dims(conf)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    # the chip donates the state into every step; this CPU process
    # would not, and the analysis would count the pool twice
    ex._donation_supported = lambda: True
    cfg = dataclasses.replace(bm.arch_config(conf, "xla"),
                              kernel_impl=KernelDispatch("pallas"))

    def shapes(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=chip), tree)

    params = shapes(jax.eval_shape(lambda k: bm.build(k, d),
                                   jax.random.PRNGKey(0)))
    for pages in map(int, argv[1:]):
        ecfg = dataclasses.replace(bm.engine_config(conf, "xla"),
                                   n_pages=pages)
        exe = ex.LocalExecutor(params, cfg, ecfg)
        state = shapes(jax.eval_shape(
            lambda: T.init_decode_state_paged(cfg, ecfg.slots, pages,
                                              ecfg.page_tokens)))
        B, C, P = ecfg.slots, ecfg.chunk, ecfg.table_pages

        def s(shape, dtype=jnp.int32):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

        progs = {
            "decode": exe._decode.lower(
                params, s((B,)), s((B,), bool), s((B,)), s((B, P)), s((B,)),
                None, None, state),
            "chunk": exe._chunk.lower(
                params, s((B, C)), s((B,)), s((B,), bool), s((B,)),
                s((B, P)), s((B,)), None, None, state),
            "page_copy": exe._copy.lower(state["blocks"], s((B,)),
                                         s((B,))),
        }
        pool = sum(int(a.size) * a.dtype.itemsize
                   for a in jax.tree.leaves(state["blocks"]))
        out = {"pages": pages, "pool_bytes": pool}
        for name, low in progs.items():
            m = low.compile().memory_analysis()
            out[name] = {"argument_bytes": m.argument_size_in_bytes,
                         "output_bytes": m.output_size_in_bytes,
                         "alias_bytes": m.alias_size_in_bytes,
                         "temp_bytes": m.temp_size_in_bytes}
        print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
