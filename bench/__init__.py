"""Chip benchmark of the CLOVER serving engine (see ``BENCHMARK.json``).

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell.  Everything a cell needs is found by
name: its configuration in ``configs/``, its traffic mix in
``traffic/``, the mix's arrival process in ``arrivals/``, its
correctness limit in ``limits/`` and each metric's reader in
``metrics/``.
"""
import importlib.util
from pathlib import Path


def load_module(root: Path, kind: str, name: str):
    """``<root>/bench/<kind>/<name>.py`` as a module: how a metric's
    reader or a mix's arrival process is found by its name alone."""
    path = root / "bench" / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
