"""Benchmark entry point: one harness per paper table/figure.

  python -m benchmarks.run                 # all, bench scale
  python -m benchmarks.run --only table1
  python -m benchmarks.run --scale test    # quick CI pass

Outputs one CSV per harness under benchmarks/artifacts/ plus a stdout
summary.
"""
from __future__ import annotations

import argparse
import csv
import os
import time

ART = os.path.join(os.path.dirname(__file__), "artifacts")


def _write_csv(name: str, rows: list[dict]) -> None:
    if not rows:
        return
    os.makedirs(ART, exist_ok=True)
    keys = sorted({k for r in rows for k in r})
    with open(os.path.join(ART, name + ".csv"), "w", newline="") as f:
        w = csv.DictWriter(f, fieldnames=keys)
        w.writeheader()
        w.writerows(rows)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    choices=["table1", "workload", "ablation", "serving"])
    ap.add_argument("--scale", default="bench",
                    choices=["test", "bench", "large"])
    args = ap.parse_args()
    todo = [args.only] if args.only else [
        "table1", "ablation", "workload", "serving"]

    for name in todo:
        t0 = time.time()
        print(f"\n===== {name} =====")
        if name == "table1":
            from benchmarks import table1
            _write_csv("table1", table1.run(args.scale))
        elif name == "ablation":
            from benchmarks import ablation
            _write_csv("ablation", ablation.run(args.scale))
        elif name == "workload":
            from benchmarks import workload
            _write_csv("workload", workload.run())
        elif name == "serving":
            from benchmarks import serving
            n = 32 if args.scale != "test" else 8
            _write_csv("serving", serving.run(n_requests=n))
        print(f"===== {name} done in {time.time() - t0:.1f}s =====")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
