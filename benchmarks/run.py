"""Paper-reproduction aggregator: the Table-2 accuracy grid and the
Figure-4 / Appendix-B softmax code-range count.

    PYTHONPATH=src python -m benchmarks.run [--quick]

Prints a `name,seconds,derived` CSV summary line per section after each
section's own table. ``--quick`` shrinks the Table-2 fine-tuning budget.
Speed is measured on the chip by ``bench/run.py``, not here.
"""
from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    args = ap.parse_args()
    steps = 60 if args.quick else 120
    stride = 6 if args.quick else 4
    summary = []

    def run(name, fn):
        t0 = time.time()
        print(f"\n=== {name} " + "=" * max(1, 60 - len(name)))
        derived = fn()
        dt = time.time() - t0
        summary.append((name, dt, derived))

    from benchmarks import softmax_range, table2_clue

    def _table2():
        rows = table2_clue.main(steps=steps, stride=stride)
        return f"{len(rows)} grid points"

    def _softmax():
        r = softmax_range.collect()
        return (f"softmax unused {r['softmax_unused']}/256; "
                f"mha unused {r['mha_unused']}/256; "
                f"unsigned fix {r['softmax_unsigned_unused']}/256")

    run("table2_clue (paper Table 2)", _table2)
    run("softmax_range (paper Figure 4 / Appx B)", _softmax)

    print("\n=== summary csv " + "=" * 44)
    print("name,seconds,derived")
    for name, dt, derived in summary:
        print(f"{name},{dt:.1f},{derived}")


if __name__ == "__main__":
    main()
