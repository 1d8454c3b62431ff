"""Readings that set a cell's correctness limit, on the chip.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 \\
        --seconds <s>

For each seed, in one process: a whole run of the cell (set-up, window,
check), then the float8 control (``bench/reference.py``: the family's
reference computed with both operands of every product rounded to
float8, in the program's place) through the same ``correct.check``,
with the cell's limits, on the same sample of served requests.  Prints
one JSON line per seed: the program's check (``correct`` true, its
widest gap a lower reading) and the control's (``correct`` false, its
widest gap an upper reading).  The benchmark's own runs never run
this.
"""
import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    from bench import correct, harness, load_family, reference
    cell = harness.open_cell(ROOT, args.workload, "bench/control.py")
    conf = harness.load_json(ROOT, "bench", "configs",
                             f"{cell['config']}.json")
    mix = harness.load_json(ROOT, "bench", "traffic",
                            f"{cell['traffic']}.json")
    limits = harness.load_json(ROOT, "bench", "limits",
                               f"{cell['name']}.json")
    fam = load_family(ROOT, conf)
    d = fam.dims(conf)
    for seed in (int(s) for s in args.seeds.split(",")):
        got = {}
        run = harness.run_cell(cell, seed, args.seconds, False,
                               hooks={"served": lambda r: got.update(rows=r)})
        t0 = time.monotonic()
        ctrl = correct.check(seed, fam, d, got["rows"],
                             mix["check_requests"], mix["max_total"], limits,
                             gaps=reference.control_gaps)
        print(json.dumps({"seed": seed,
                          "program": {"correct": run["correct"],
                                      **run["checks"]},
                          "control": {"correct": ctrl["correct"],
                                      **ctrl["limits"]},
                          "control_s": time.monotonic() - t0,
                          "metrics": run["metrics"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
