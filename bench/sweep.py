"""Find an open-loop cell's knee: the same cell at several arrival
rates, in one process, on the chip.

    python3 bench/sweep.py --workload <cell> --rates 1,2,4 --seconds <s>

Each rate runs from a throwaway copy of the benchmark's files whose mix
has that ``rate_per_s``.  Prints one JSON line per rate with the
end-to-end metrics and the requests due and served.  The knee is the
highest rate at which the queue does not grow through the window; the
cell's mix then fixes its rate below it.  The benchmark's own runs
never run this.
"""
import argparse
import json
import shutil
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    from bench import harness
    cell = harness.open_cell(ROOT, args.workload, "bench/sweep.py")
    with tempfile.TemporaryDirectory() as tmp:
        root = Path(tmp)
        shutil.copy(ROOT / "BENCHMARK.json", root)
        shutil.copytree(ROOT / "bench", root / "bench",
                        ignore=shutil.ignore_patterns("__pycache__"))
        mix_path = root / "bench" / "traffic" / f"{cell['traffic']}.json"
        mix = json.loads(mix_path.read_text())
        for rate in (float(r) for r in args.rates.split(",")):
            mix_path.write_text(json.dumps(dict(mix, rate_per_s=rate)))
            run = harness.run_cell(cell, args.seed, args.seconds, False,
                                   root=root)
            print(json.dumps({"rate_per_s": rate,
                              "attempted": run["attempted"],
                              "correct": run["correct"],
                              "metrics": run["metrics"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
