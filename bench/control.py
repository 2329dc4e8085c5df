"""Readings that set a cell's limit: on each seed, the program's number and
its control's, in one process.

    python3 bench/control.py --workload <cell> --seeds 11,12,13 --seconds 10

For each seed this builds the cell as a run does, serves the seed's
schedule for ``--seconds``, frees the program, and reads the number that
decides ``correct`` twice from the same finished requests: once for what
the program served (the lower reading) and once for each control, the
configuration's reference put in the program's place with its int8 GEMMs
in int4, and with its float32 parts in bfloat16 (the upper readings). One
JSON row per seed. It needs a TPU, as a run does.
"""
import argparse
import gc
import json
import sys
import time

import run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    import drive
    import system as system_mod
    import traffic as traffic_mod
    cell = run.spec.cell(args.workload)
    try:
        run.devices(cell.chips)
    except run.NoChip as e:
        run.log(f"control: {e}")
        return 2
    run.enable_cache()
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        sysm = system_mod.build(cell, seed, log=run.log)
        system_mod.warm(sysm, log=run.log)
        max_len, arch = sysm.max_len, sysm.arch
        items = traffic_mod.schedule(cell.traffic, args.seconds, seed,
                                     arch.vocab_size)
        win = drive.run(sysm, items, args.seconds, cell.traffic["drain_s"])
        sysm.engine = None
        del sysm
        gc.collect()
        row = {"seed": seed}
        for control in (None, "int4", "bf16"):
            row[control or "program"] = run.compare(
                cell, win.records, seed, max_len, arch, control=control)
        print(json.dumps({**row,
                          "attempted": len(win.records),
                          "failed": sum(not r.ok for r in win.records),
                          "seconds": time.perf_counter() - t}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
