"""The control of ``check.py``'s comparison at a cell's own size.

    python bench/control.py --workload dense.konect-small --seeds 11 12 13

For each seed it takes the requests a run of the cell would sample (the
first ``check.sample`` of the seed's request sequence), answers them with
the control in the program's place (the plain reference with the
guarantee broken: half of each graph's root subtrees, reported as
complete), and prints the numbers the comparison reads, beside their
limits, as JSON lines.  It needs no chip and starts no JAX; the
benchmark's own runs never run it.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if sys.path[0] == os.path.dirname(os.path.abspath(__file__)):
    sys.path[0] = ROOT

from bench import check, reference, traffic  # noqa: E402
from bench.run import load_cell  # noqa: E402


def control_readings(workload: str, seed: int, workers: int,
                     roots_share: float = 0.5) -> dict:
    mix = load_cell(workload)[3]
    graphs = traffic.generate(mix, seed, mix["check"]["sample"])
    rows = [g.rows for g in graphs]
    t0 = time.perf_counter()
    answers = reference.enumerate_many(rows, workers=workers,
                                       roots_share=roots_share)
    checks = check.compare([(r, n, cs) for r, (n, cs) in zip(rows, answers)],
                           missing=0, not_done=0, workers=workers)
    return dict(workload=workload, seed=seed, correct=check.passes(checks),
                seconds=time.perf_counter() - t0, check=check.as_json(checks))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    workers = len(os.sched_getaffinity(0))
    for seed in args.seeds:
        print(json.dumps(control_readings(args.workload, seed, workers)),
              flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
