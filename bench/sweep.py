"""Finds a fixed-rate cell's knee on the chip: one build, a few offered
rates, one printed row each.

    python3 bench/sweep.py --workload <cell> --rates 400,800,1200 --seconds 10

Each rate replaces the traffic file's Poisson rate. A row gives the requests due and finished,
the median latency of the requests due in each quarter of the window
(encoder: to the logits; decode: to the first token), and the backlog —
requests due but not yet in service — at the middle and at the close of
the window. A rate is sustained when the backlog does not grow: at the
close it is at most 1.5 times the middle's plus 1% of the requests due
(at least 2). The knee is the highest rate at which it and every lower
rate are sustained. It needs a TPU, as a run does.
"""
import argparse
import copy
import gc
import json
import math
import sys

import run


def latency(rec, encoder: bool) -> float:
    if not rec.ok:
        return math.inf
    return (rec.done if encoder else rec.token_times[0]) - rec.item.due


def backlog(win, t: float, encoder: bool) -> int:
    """Requests due by ``t`` whose service had not started at ``t``."""
    field = "served" if encoder else "admitted"
    return sum(1 for r in win.records if r.item.due <= t
               and not getattr(r, field) <= t)


def row(rate: float, win, encoder: bool) -> dict:
    import stats
    recs = win.records
    quarters = []
    for k in range(4):
        lo, hi = k * win.seconds / 4, (k + 1) * win.seconds / 4
        xs = [latency(r, encoder) for r in recs if lo <= r.item.due < hi]
        quarters.append(1e3 * stats.quantile(xs, 0.5) if xs else None)
    mid = backlog(win, win.seconds / 2, encoder)
    end = backlog(win, win.seconds, encoder)
    return {"rate": rate, "due": len(recs),
            "finished": sum(r.ok for r in recs),
            "p50_ms_by_quarter": quarters,
            "p95_ms": 1e3 * stats.quantile([latency(r, encoder)
                                            for r in recs], 0.95),
            "backlog_mid": mid, "backlog_close": end,
            "sustained": end <= 1.5 * mid + max(2, 0.01 * len(recs))}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    import drive
    import system as system_mod
    import traffic as traffic_mod
    cell = run.spec.cell(args.workload)
    try:
        run.devices(cell.chips)
    except run.NoChip as e:
        run.log(f"sweep: {e}")
        return 2
    run.enable_cache()
    sysm = system_mod.build(cell, args.seed, log=run.log)
    system_mod.warm(sysm, log=run.log)
    encoder = cell.kind == "encoder"
    for rate in (float(r) for r in args.rates.split(",")):
        traffic = copy.deepcopy(cell.traffic)
        traffic["arrivals"] = {"process": "poisson", "rate": rate}
        items = traffic_mod.schedule(traffic, args.seconds, args.seed,
                                     sysm.arch.vocab_size)
        win = drive.run(sysm, items, args.seconds, traffic["drain_s"])
        print(json.dumps(row(rate, win, encoder)), flush=True)
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
