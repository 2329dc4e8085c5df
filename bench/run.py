"""The benchmark's one command: one run of one cell on the chips it asks for.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

A run builds the configuration's model from the seed (weights made on the
device, the program's synthetic calibration, the SAMP plan), warms every
shape the cell's traffic can reach, offers the traffic file's open-loop
schedule for ``--seconds``, follows the requests due in the window to their
end, checks what the timed path returned against the configuration's plain
reference, and prints one JSON line last. ``--trace 1`` profiles a few
seconds in the middle of the window and reports the cell's per-layer
metrics and a breakdown instead of the end-to-end ones.

It needs a TPU: on any other platform, or with fewer chips than the cell
asks for, it exits non-zero and prints no result. A compile inside the
window also makes it exit non-zero.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
for p in (str(ROOT / "src"), str(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)
import stats  # noqa: E402
import spec  # noqa: E402

TRACE_DIR = ROOT / ".bench_trace"
#: the traced part of a --trace 1 window: it starts this far in, as a share
#: of the window, and lasts this share of it, at most TRACE_MAX_S
TRACE_FROM, TRACE_SHARE, TRACE_MAX_S = 0.4, 0.3, 3.0


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class NoChip(RuntimeError):
    pass


def devices(chips: int):
    """The JAX devices, or NoChip when they are not ``chips`` TPUs."""
    import jax
    devs = jax.devices()
    dev = devs[0]
    log(f"[device] platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devs)}")
    if dev.platform != "tpu":
        raise NoChip(f"needs a TPU, found {dev.platform!r}")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, found {len(devs)}")
    return devs


class RunData:
    """What a per-layer reader gets: the cell, the window's records, the
    reduced trace (or None) and the device's peaks."""

    def __init__(self, cell, window, trace, peaks, max_len):
        self.cell, self.window, self.trace = cell, window, trace
        self.peaks, self.max_len = peaks, max_len

    @property
    def config(self) -> dict:
        return self.cell.config

    def steps_in_trace(self) -> list:
        """Model steps wholly inside the traced part of the window."""
        if self.window.trace_at is None:
            return []
        a, b = self.window.trace_at
        return [s for s in self.window.steps if s[0] >= a and s[1] <= b]


def end_to_end(cell, win, setup_s: float) -> dict:
    recs = win.records
    inf = math.inf
    out = {"setup_s": setup_s}
    names = {m["name"] for m in cell.end_to_end}
    if "enc_p95_ms" in names:
        lat = [r.done - r.item.due if r.ok else inf for r in recs]
        out["enc_p95_ms"] = 1e3 * stats.quantile(lat, 0.95)
    if "ttft_p90_ms" in names:
        lat = [r.token_times[0] - r.item.due if r.ok else inf for r in recs]
        out["ttft_p90_ms"] = 1e3 * stats.quantile(lat, 0.90)
    if "itl_p95_ms" in names:
        gaps = []
        for r in recs:
            t = r.token_times
            gaps += [b - a for a, b in zip(t, t[1:])] if r.ok else [inf]
        out["itl_p95_ms"] = 1e3 * stats.quantile(gaps, 0.95)
    if "tokens_per_s" in names:          # real input tokens classified
        out["tokens_per_s"] = sum(len(r.item.tokens) for r in recs
                                  if r.ok and r.done <= win.seconds) \
            / win.seconds
    return out


def per_layer(data: RunData) -> dict:
    out = {}
    for m in data.cell.per_layer:
        value = spec.layer_reader(m["name"]).read(data)
        if value is not None:
            out[m["name"]] = value
    return out


def breakdown(trace) -> dict:
    from tracereduce import idle_by_activity, op_seconds, top
    return {"device_ops": top(op_seconds(trace)),
            "idle_gaps": top(idle_by_activity(trace))}


def print_window_lines(win, items, kind: str) -> None:
    late = [r.submitted - r.item.due for r in win.records
            if not math.isnan(r.submitted)]
    if late:
        log(f"[window] generator lateness (submit - due): median "
            f"{1e3 * stats.quantile(late, 0.5):.3f} ms, max "
            f"{1e3 * max(late):.3f} ms over {len(late)} requests")
    log(f"[window] {len(items)} requests due in {win.seconds:g}s; "
        f"{sum(r.ok for r in win.records)} finished; "
        f"{len(win.steps)} model steps")
    traces = win.counters["after"]["traces"] - win.counters["before"]["traces"]
    log(f"[window] compiles inside the window: {win.compiles} (runtime "
        f"traces {traces})" + (f": {win.compiled}" if win.compiles else ""))
    recs, inf = win.records, math.inf
    if kind == "encoder":
        tails = {"latency": [r.done - r.item.due if r.ok else inf
                             for r in recs]}
    else:
        tails = {"ttft": [r.token_times[0] - r.item.due if r.ok else inf
                          for r in recs],
                 "itl": [b - a for r in recs
                         for a, b in zip(r.token_times, r.token_times[1:])]}
    for name, xs in tails.items():
        if xs:
            log(f"[window] {name} ms at p50/p90/p95/p99: " + " ".join(
                f"{1e3 * stats.quantile(xs, q):.3f}"
                for q in (0.5, 0.9, 0.95, 0.99)))


def kernel_census(engine) -> None:
    for key, compiled in engine.runtime.executables():
        n = compiled.as_text().count("tpu_custom_call")
        log(f"[census] executable {key[0]}{tuple(key[2:4])}: {n} "
            f"tpu_custom_call")


def compare(cell, records, seed: int, max_len: int, arch,
            control=None) -> dict:
    """The number that decides ``correct`` (see ``check.py``), from the
    configuration's reference on the benchmark's float weights for
    ``seed``; ``control`` (``"int4"`` or ``"bf16"``) reads it from the
    reference at that lower precision instead."""
    import check
    import system as system_mod
    ref = spec.reference(cell.config)
    params = system_mod.float_params(cell.config, arch, seed)
    kw = {} if cell.kind == "encoder" else {
        "rows_pad": max(c["output"][1] for c in cell.traffic["mix"])}
    return check.number(cell.kind, ref, params, cell.config, records, seed,
                        max_len, control=control, **kw)


def enable_cache() -> None:
    """JAX's persistent compilation cache where the program puts it (the
    directory ``JAX_COMPILATION_CACHE_DIR`` names, else the checkout's fixed
    ``.jax_cache``), for every program however short its compile: only a
    cell's first run in a checkout compiles."""
    import jax
    from repro.launch.cli import enable_compilation_cache
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    log(f"[setup] compilation cache: {enable_compilation_cache()}")


def execute(cell, seed: int, seconds: float, trace: bool, devs) -> dict:
    """Everything after the look for a chip; returns the result line."""
    import drive
    import system as system_mod
    import traffic as traffic_mod
    counter = drive.CompileCounter()
    sysm = system_mod.build(cell, seed, log=log)
    system_mod.warm(sysm, log=log)
    max_len, arch = sysm.max_len, sysm.arch
    items = traffic_mod.schedule(cell.traffic, seconds, seed,
                                 arch.vocab_size)
    trace_dir = None
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        trace_dir = TRACE_DIR
    win = drive.run(sysm, items, seconds, cell.traffic["drain_s"],
                    trace_dir=trace_dir,
                    trace_start=TRACE_FROM * seconds,
                    trace_len=min(TRACE_SHARE * seconds, TRACE_MAX_S),
                    counter=counter)
    setup_s = win.opened - T_START
    log(f"[setup] {setup_s:.3f}s to the window")
    dev = devs[0]
    mem = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
              for d in devs[:cell.chips])
    print_window_lines(win, items, cell.kind)
    if trace:
        kernel_census(sysm.engine)
    result = {"correct": False, "attempted": len(win.records),
              "failed": sum(not r.ok for r in win.records)}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs[:cell.chips]), "memory_peak_bytes": int(mem)}
    if trace:
        import tracereduce as trace_mod
        red = trace_mod.load(trace_mod.find(trace_dir))
        a, b = win.trace_at
        device["busy_s"] = trace_mod.busy_seconds(red)
        device["window_s"] = b - a
        data = RunData(cell, win, red, spec.peaks(dev.device_kind), max_len)
        metrics = per_layer(data)
        result["breakdown"] = breakdown(red)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    else:
        metrics = end_to_end(cell, win, setup_s)
    units = {m["name"]: m["unit"] for m in cell.end_to_end + cell.per_layer}
    result["metrics"] = {k: {"value": v, "unit": units[k]}
                         for k, v in metrics.items()}
    result["device"] = device
    import check
    narrow = check.narrow_floats(sysm.engine.params)
    # the program's state goes before the reference runs
    sysm.engine = None
    del sysm
    gc.collect()
    name = cell.config["check"]["name"]
    limit = cell.config["check"]["limit"]
    got = compare(cell, win.records, seed, max_len, arch)
    value = got["value"]
    result["correct"] = bool(limit is not None and math.isfinite(value)
                             and value <= limit
                             and win.compiles == 0 and narrow == 0)
    log(f"[check] sample: {got}")
    log(f"[check] {name} {value!r} limit {limit!r}")
    log(f"[check] compiles {win.compiles} limit 0")
    log(f"[check] narrow_floats {narrow} limit 0")
    result["checks"] = {name: {"value": value, "limit": limit},
                        "compiles": {"value": win.compiles, "limit": 0},
                        "narrow_floats": {"value": narrow, "limit": 0}}
    return result


def finite(x):
    """The result with every number that is not finite (a tail that holds a
    failed request, a comparison over non-finite logits) as null, so that
    the line is strict JSON."""
    if isinstance(x, dict):
        return {k: finite(v) for k, v in x.items()}
    if isinstance(x, list):
        return [finite(v) for v in x]
    if isinstance(x, float) and not math.isfinite(x):
        return None
    return x


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = spec.cell(args.workload)
    try:
        devs = devices(cell.chips)
    except NoChip as e:
        log(f"bench: {e}")
        return 2
    enable_cache()
    result = execute(cell, args.seed, args.seconds, bool(args.trace), devs)
    compiles = result["checks"]["compiles"]["value"]
    print(json.dumps(finite(result)), flush=True)
    if compiles:
        log(f"bench: {compiles} compile(s) inside the window")
        return 3
    return 0


if __name__ == "__main__":
    sys.exit(main())
