"""Chip benchmark of the CLOVER serving engine (see ``BENCHMARK.json``).

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell.  Everything a cell needs is found by
name: its configuration in ``configs/``, the configuration's
architecture in ``families/``, its traffic mix in ``traffic/``, the
mix's arrival process in ``arrivals/``, its correctness limit in
``limits/`` and each metric's reader in ``metrics/``.

A configuration file names its family by the top-level key ``arch``
(``"decoder"`` where it has none): ``families/<arch>.py``, a module
with this interface, where ``d`` is what ``dims`` returns:

- ``dims(conf) -> dict``: the sizes; at least ``L`` (layers), ``V``
  (the vocabulary the traffic draws from) and ``dtype`` (the served
  one), the rest the family's own;
- ``arch_config(conf, kernel_impl)``: the program's ``ArchConfig``,
  nested sections included;
- ``build(key, d)``: every weight from one base key, traceable, as the
  program takes them; ``layer_params(key, d, layer)`` and
  ``top_params(key, d)``: one layer's leaves, and those outside the
  layers, from the keys of ``bench/model.py``, so the reference can
  draw one layer again alone; ``make_params(seed, d, tp)``: every
  weight on the device, by ``bench.model.draw``;
- ``logits(seed, d, tokens, precision="f32")``: the plain reference,
  (B, T) tokens to (B, T, V) float32 logits at ``highest``;
  ``precision="fp8"`` is the control (``bench/reference.py``);
- work counts from logical shapes (``bench/work.py``):
  ``decode_step(d, held) -> (FLOPs, bytes)``,
  ``chunk_step_flops(d, rows) -> FLOPs``, ``weight_bytes(d)``,
  ``kv_bytes_per_token(d)``, and ``kernel_work``: a traced kernel's
  name -> its ``(FLOPs, bytes)`` per call, ``fn(d, held)``.
"""
import importlib.util
from pathlib import Path

DEFAULT_ARCH = "decoder"


def load_module(root: Path, kind: str, name: str):
    """``<root>/bench/<kind>/<name>.py`` as a module: how a metric's
    reader, a mix's arrival process or a configuration's family is
    found by its name alone."""
    path = root / "bench" / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_family(root: Path, conf: dict):
    """The family module of a configuration (see above)."""
    return load_module(root, "families", conf.get("arch", DEFAULT_ARCH))
