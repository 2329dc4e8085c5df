"""Bring-up smoke: the SAMP serving path, end to end, on a TPU.

    python chip_smoke.py               # one chip: encoder + decode phases
    python chip_smoke.py --chips 4     # four chips: the meshed phases only

One process drives every phase through the normal serving entry points
(``launch.serve.build_model`` and the engines), at published widths with
random weights and synthetic calibration made from ``--seed``:

* **encoder** — bert-base with a tnews head under the fully quantized plan
  upgraded to the whole-layer int8 dataflow, served by
  ``EncoderServeEngine`` on the ``fused`` backend (fused_embed,
  quant_linear, addnorm_quant, quant_flash_attention) over three length
  buckets up to 512 tokens;
* **decode** — qwen2-0.5b under the FFN-quantized plan, served by
  ``ServeEngine`` on the ``fused`` backend with a paged int8_per_token KV
  cache (quant_linear, decode_attention), greedy.

Each phase serves the same quantized params and requests on the
``reference`` backend in the same process and compares: encoder logits;
decode first-step logits and greedy tokens. It fails when an executable the
fused Runtime built holds no Pallas kernel (``tpu_custom_call``).

``--chips 4`` runs only what exists across chips, each against unmeshed
runs of the same requests. On mesh 4,1 every device runs the unmeshed
program on its share of the batch, so bert-base and qwen2-0.5b decode
there must be bit-identical to unmeshed serving at the per-device batch
(2 encoder rows; 1 decode slot: same tokens, same logits behind each).
bert-base at mesh 1,4 (reference path) must be within the tolerance
below of unmeshed reference serving.

Tolerance. The fused and reference backends compute the same int8
arithmetic (int32 GEMM accumulation, the same calibrated scales), and a
mesh changes only how rows are spread over devices; two such runs differ
only where float32 rounding order moves a value across an int8 / uint8
rounding boundary and flips its code by one step. That is bounded by the
quantization itself: each comparison's tolerance is the distance between
the int8 model and the float model on the same requests, measured in the
same run. Planted kernel faults (a wrong head's scales in decode
attention, a neighbouring table row in fused_embed, padded keys left
unmasked in quantized flash attention) exceed it at reduced widths; see
PERF.md. With random weights the top-2 logit margin is often tiny, so
greedy tokens must agree except at a step whose reference margin is
within twice the measured logit gap (itself held to the tolerance), where
the two runs may legitimately pick different tokens; the rest of that
sequence is then not compared.

No CPU fallback: without a TPU the script exits non-zero. The last line of
standard output is one JSON object naming the device. Nothing here is a
speed measurement; the seconds printed are set-up times, compiles included.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parent
OUT = ROOT / "chiprun_out"

ENCODER_BUCKETS = ((17, 32, 5), (65, 128, 5), (257, 512, 6))  # lo, hi, n
DECODE_PROMPTS = (16, 128)
DECODE_REQUESTS = 6
NEW_TOKENS = 16


def log(msg: str) -> None:
    print(msg, flush=True)


class PhaseError(RuntimeError):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise PhaseError(msg)


# ---------------------------------------------------------------------------
# shared checks
# ---------------------------------------------------------------------------


def kernel_census(name: str, runtime, *, need_kernels: bool) -> None:
    """Print the tpu_custom_call count of every executable ``runtime``
    built; with ``need_kernels`` a count of 0 fails the phase."""
    exes = list(runtime.executables())
    check(exes, f"{name}: the runtime built no executable")
    for key, compiled in exes:
        n = compiled.as_text().count("tpu_custom_call")
        kind, shape = key[0], key[2:4]
        log(f"[{name}] executable {kind}{tuple(shape)}: "
            f"{n} tpu_custom_call")
        if need_kernels:
            check(n > 0, f"{name}: executable {kind}{tuple(shape)} holds "
                         f"no Pallas kernel")


def pool_census(name: str, runtime, *, in_place: bool) -> None:
    """Print the relayout copies of a page-pool leaf in every decode
    executable ``runtime`` built; ``in_place`` (the fused backend on int8
    pages) fails the phase on any."""
    for key, n in runtime.pool_copies().items():
        log(f"[{name}] executable decode{tuple(key[2:3])}: {n} copies of a "
            f"page-pool leaf")
        if in_place:
            check(n == 0, f"{name}: the decode step relayouts its page "
                          f"pool {n} times")


def logit_gap(name: str, got, want, bound=None) -> float:
    """max |got - want| over max |want|; fails above ``bound`` (None:
    report only)."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    check(got.shape == want.shape, f"{name}: shape {got.shape} != "
                                   f"{want.shape}")
    check(np.isfinite(got).all(), f"{name}: non-finite logits")
    scale = float(np.abs(want).max())
    gap = float(np.abs(got - want).max()) / max(scale, 1e-30)
    log(f"[{name}] logits {got.shape}: max|diff| / max|ref| = {gap:.3e}"
        + ("" if bound is None else f" (tolerance {bound:.3e})"))
    if bound is not None:
        check(gap <= bound, f"{name}: logits differ by {gap:.3e} of the "
                            f"logit range, above the tolerance {bound:.3e}")
    return gap


# ---------------------------------------------------------------------------
# encoder
# ---------------------------------------------------------------------------


def build_encoder(cfg, seed: int):
    """bert + tnews head: the float model, and the same weights quantized
    by the full plan upgraded to the whole-layer int8 dataflow (handed to
    build_model as a plan file). Returns ((params, plan) float,
    (params, plan) int8)."""
    from repro.core.precision import make_policy
    from repro.core.samp import int8_dataflow_variant
    from repro.data.pipeline import make_task
    from repro.launch.serve import build_model
    task = make_task("tnews", vocab_size=cfg.vocab_size,
                     seq_len=cfg.max_position)
    head = ("cls", task.n_classes)
    plan = int8_dataflow_variant(make_policy(cfg, "full"))
    OUT.mkdir(exist_ok=True)
    plan_file = OUT / f"{cfg.name}-full-int8flow.json"
    plan.save(str(plan_file))
    fparams, fplan, _ = build_model(cfg, "float", seed=seed, head=head,
                                    log=log)
    params, exec_plan, _ = build_model(cfg, seed=seed, head=head,
                                       plan_file=str(plan_file), log=log)
    return (fparams, fplan), (params, exec_plan)


def encoder_requests(cfg, seed: int):
    rng = np.random.default_rng(seed)
    reqs = []
    for lo, hi, n in ENCODER_BUCKETS:
        for _ in range(n):
            length = int(rng.integers(lo, min(hi, cfg.max_position) + 1))
            reqs.append(rng.integers(1, cfg.vocab_size, size=length).tolist())
    order = rng.permutation(len(reqs))
    return [reqs[i] for i in order]


def serve_encoder(cfg, params, plan, reqs, *, backend: str, mesh=None,
                  max_batch: int = 8):
    """Serve ``reqs`` through an EncoderServeEngine whose every
    micro-batch compiles at ``max_batch`` rows; returns (logits by
    request, engine, seconds including compiles)."""
    from repro.serve import EncoderRequest, EncoderServeEngine, Runtime
    from repro.toolkit.registry import get_target
    target = get_target("cls")
    runtime = Runtime(cfg, plan, head=lambda p, h: target.apply(p, h, cfg),
                      min_batch=max_batch, max_len=cfg.max_position,
                      backend=backend, mesh=mesh)
    eng = EncoderServeEngine(cfg, params, plan, target=target,
                             max_batch=max_batch, max_len=cfg.max_position,
                             runtime=runtime)
    t0 = time.perf_counter()
    for uid, toks in enumerate(reqs):
        eng.submit(EncoderRequest(uid=uid, tokens=toks))
    done = eng.run()
    secs = time.perf_counter() - t0
    check(len(done) == len(reqs), f"encoder/{backend}: {len(done)} of "
                                  f"{len(reqs)} requests retired")
    logits = np.stack([np.asarray(r.logits) for r in
                       sorted(done, key=lambda r: r.uid)])
    return logits, eng, secs


def encoder_phase(cfg, seed: int) -> None:
    t0 = time.perf_counter()
    (fparams, fplan), (params, plan) = build_encoder(cfg, seed)
    log(f"[encoder] {cfg.name} d_model={cfg.d_model} layers="
        f"{cfg.num_layers} vocab={cfg.vocab_size}: built + calibrated in "
        f"{time.perf_counter() - t0:.1f}s")
    reqs = encoder_requests(cfg, seed)
    log(f"[encoder] {len(reqs)} requests, lengths "
        f"{sorted(len(r) for r in reqs)}")
    fused, eng, secs = serve_encoder(cfg, params, plan, reqs,
                                     backend="fused")
    log(f"[encoder] fused: {eng.stats['batches']} micro-batches, "
        f"{eng.stats['runtime_traces']} compile(s), {secs:.1f}s with "
        f"compiles")
    kernel_census("encoder", eng.runtime, need_kernels=True)
    ref, reng, rsecs = serve_encoder(cfg, params, plan, reqs,
                                     backend="reference")
    log(f"[encoder] reference: {reng.stats['runtime_traces']} compile(s), "
        f"{rsecs:.1f}s with compiles")
    flt, _, _ = serve_encoder(cfg, fparams, fplan, reqs,
                              backend="reference")
    bound = logit_gap("encoder int8 reference vs float", ref, flt)
    logit_gap("encoder fused vs reference", fused, ref, bound)
    exact = int(sum(np.array_equal(a, b) for a, b in zip(fused, ref)))
    agree = int((fused.argmax(-1) == ref.argmax(-1)).sum())
    log(f"[encoder] fused logits bit-identical to reference on "
        f"{exact}/{len(reqs)} requests; predictions agree on "
        f"{agree}/{len(reqs)}")


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------


def decode_requests(cfg, seed: int):
    from repro.serve import Request
    rng = np.random.default_rng(seed + 1)
    lo, hi = DECODE_PROMPTS
    return [Request(uid=i, prompt=rng.integers(
                1, cfg.vocab_size, size=int(rng.integers(lo, hi + 1)))
                .tolist(), max_tokens=NEW_TOKENS)
            for i in range(DECODE_REQUESTS)]


def build_decoder(cfg, seed: int):
    """The float model and the same weights under the FFN-quantized plan:
    ((params, plan) float, (params, plan) int8)."""
    from repro.launch.serve import build_model
    fparams, fplan, _ = build_model(cfg, "float", seed=seed, log=log)
    params, plan, _ = build_model(cfg, "ffn", seed=seed, log=log)
    return (fparams, fplan), (params, plan)


def decode_engine(cfg, params, plan, seed: int, *, backend: str,
                  mesh=None, kv_cache: str = "int8_per_token",
                  slots: int = 4):
    """A ServeEngine on paged KV (16-token pages, 4 slots) with the
    phase's requests submitted; returns (engine, requests)."""
    from repro.serve import ServeEngine
    eng = ServeEngine(cfg, params, plan, batch_slots=slots, max_len=256,
                      backend=backend, mesh=mesh, page_size=16,
                      kv_cache=kv_cache)
    reqs = decode_requests(cfg, seed)
    for r in reqs:
        eng.submit(r)
    return eng, reqs


def _record_ticks(eng, rows=None) -> dict:
    """Wrap the engine's decode step to keep each tick's logits,
    active-slot mask, and each occupied slot's (request, output count)
    as the step starts; with ``rows`` (a dict), also every active slot's
    logits row by (request uid, tokens consumed before the step)."""
    inner, tick = eng._decode, {}

    def recording(params, caches, tokens, pos, active, pages=None):
        tick["held"] = {s: (r, len(r.output))
                        for s, r in enumerate(eng.sched.active)
                        if r is not None}
        at = {s: int(eng.sched.cursor[s]) for s in tick["held"]}
        logits, caches = inner(params, caches, tokens, pos, active, pages)
        tick["logits"], tick["active"] = logits, active
        if rows is not None:
            got = np.asarray(logits)
            for s, (r, _) in tick["held"].items():
                if active[s]:
                    rows[(r.uid, at[s])] = got[s]
        return logits, caches

    eng._decode = recording
    return tick


def decode_rows(eng) -> dict:
    """Serve an engine's requests to the end; returns its logits rows by
    (request uid, tokens consumed)."""
    rows = {}
    _record_ticks(eng, rows)
    while eng.sched.busy:
        eng.step()
    return rows


def decode_lockstep(runs):
    """Serve the same requests on several engines tick by tick; ``runs``
    holds (engine, requests) pairs, the last one the reference.

    The schedule depends only on prompt lengths and token counts, so every
    engine runs the same request in the same slot in every tick. Wherever
    a slot holds the same history in an engine and in the reference, the
    two logits rows are compared. Returns (first tick's logits per engine,
    tokens per engine by uid, the reference's top-1 minus top-2 margin
    behind each of its tokens by (uid, index), and per engine the largest
    row |diff|, the largest reference |logit| across its compared rows
    and the rows compared, seconds including compiles)."""
    engines = [e for e, _ in runs]
    n = len(engines) - 1
    ticks = [_record_ticks(e) for e in engines]
    firsts, margins = [None] * len(engines), {}
    worst, scale, rows = [0.0] * n, [0.0] * n, [0] * n
    t0 = time.perf_counter()
    while any(e.sched.busy for e in engines):
        for e, t in zip(engines, ticks):
            t.clear()
            e.step()
        ran = ["logits" in t for t in ticks]
        check(len(set(ran)) == 1, "engines fell out of lockstep")
        if not ran[0]:
            continue
        logits = [np.asarray(t["logits"], np.float64) for t in ticks]
        live = [np.asarray(t["active"]) for t in ticks]
        firsts = [x if f is None else f for f, x in zip(firsts, logits)]
        for s in np.flatnonzero(np.logical_and.reduce(live)):
            held = [t["held"][s] for t in ticks]
            rb, nb = held[n]
            for i, (r, k) in enumerate(held[:n]):
                if r.uid == rb.uid and r.output[:k] == rb.output[:nb]:
                    worst[i] = max(worst[i], float(np.abs(
                        logits[i][s] - logits[n][s]).max()))
                    scale[i] = max(scale[i], float(np.abs(
                        logits[n][s]).max()))
                    rows[i] += 1
            if len(rb.output) > nb:
                top2 = np.partition(logits[n][s], -2)[-2:]
                margins[(rb.uid, nb)] = float(top2[1] - top2[0])
    secs = time.perf_counter() - t0
    tokens = [{r.uid: list(r.output) for r in reqs} for _, reqs in runs]
    for toks in tokens:
        check(all(len(t) == NEW_TOKENS for t in toks.values()),
              f"not every request produced {NEW_TOKENS} tokens")
    check(len(margins) == DECODE_REQUESTS * NEW_TOKENS,
          f"{len(margins)} reference tokens attributed to ticks")
    return firsts, tokens, margins, list(zip(worst, scale, rows)), secs


def compare_tokens(name: str, got: dict, want: dict, margins: dict,
                   tie: float) -> None:
    """Greedy tokens must agree. A divergence is admitted only at a step
    where the reference's top-1/top-2 margin is within ``tie`` (two logits
    that each agree within half of it may swap order); the rest of that
    sequence then has another context and is not compared."""
    compared, ties = 0, []
    for uid in sorted(want):
        for i, (a, b) in enumerate(zip(got[uid], want[uid])):
            if a == b:
                compared += 1
                continue
            m = margins[(uid, i)]
            check(m <= tie, f"{name}: request {uid} token {i}: {a} != {b} "
                            f"with reference margin {m:.4g} > {tie:.4g}")
            ties.append((uid, i, round(m, 5)))
            break
    total = sum(len(t) for t in want.values())
    log(f"[{name}] greedy tokens: {compared}/{total} equal before any "
        f"near-tie; near-tie divergences (uid, index, margin): {ties}")


def decode_phase(cfg, seed: int) -> None:
    t0 = time.perf_counter()
    (fparams, fplan), (params, plan) = build_decoder(cfg, seed)
    log(f"[decode] {cfg.name} d_model={cfg.d_model} layers="
        f"{cfg.num_layers} kv_heads={cfg.num_kv_heads} vocab="
        f"{cfg.vocab_size}: built + calibrated in "
        f"{time.perf_counter() - t0:.1f}s")
    fused = decode_engine(cfg, params, plan, seed, backend="fused")
    flt = decode_engine(cfg, fparams, fplan, seed, backend="reference",
                        kv_cache="float")
    ref = decode_engine(cfg, params, plan, seed, backend="reference")
    log(f"[decode] {DECODE_REQUESTS} requests, prompt lengths "
        f"{[len(r.prompt) for r in fused[1]]}, {NEW_TOKENS} greedy tokens "
        f"each; fused, float and reference engines in lockstep")
    (f_first, _, r_first), (f_tok, _, r_tok), margins, stats, secs = \
        decode_lockstep([fused, flt, ref])
    (diff, scale, rows), (fdiff, fscale, frows) = stats
    gap, bound = diff / scale, fdiff / fscale
    log(f"[decode] {fused[0].stats['ticks']} ticks each, {secs:.1f}s with "
        f"compiles")
    kernel_census("decode", fused[0].runtime, need_kernels=True)
    pool_census("decode", fused[0].runtime, in_place=True)
    pool_census("decode reference", ref[0].runtime, in_place=False)
    logit_gap("decode first-step fused vs reference", f_first, r_first,
              bound)
    log(f"[decode] float model vs int8 reference, {frows} slot rows with "
        f"the same history: max|diff| / max|ref| = {bound:.3e}")
    log(f"[decode] fused vs reference, {rows} slot rows with the same "
        f"history: max|diff| / max|ref| = {gap:.3e} (tolerance "
        f"{bound:.3e})")
    check(gap <= bound, f"decode: per-tick logits differ by {gap:.3e} of "
                        f"the logit range, above {bound:.3e}")
    # two logits that each moved by at most ``diff`` can swap order only
    # where the reference's top-2 margin is within 2 * diff; ``diff`` was
    # just held to the float-vs-int8 bound, so the tie margin is too
    compare_tokens("decode fused vs reference", f_tok, r_tok, margins,
                   2 * diff)


# ---------------------------------------------------------------------------
# four chips: meshed serving against unmeshed
# ---------------------------------------------------------------------------


def place_params(name: str, cfg, mesh, params):
    """Place ``params`` by the serving sharding rules for ``mesh`` and
    report every leaf's addressable shards (the full list goes to
    chiprun_out/); fails if a leaf sits on device 0 alone."""
    import jax
    from repro.distributed.sharding import Rules
    placed = jax.device_put(
        params, Rules(cfg, mesh, fsdp=False).params_sharding(params))
    lines, split, alone = [], 0, []
    for path, leaf in jax.tree_util.tree_leaves_with_path(placed):
        shards = leaf.addressable_shards
        devs = sorted(s.device.id for s in shards)
        key = jax.tree_util.keystr(path)
        lines.append(f"{key} {tuple(leaf.shape)} -> shard "
                     f"{tuple(shards[0].data.shape)} on devices {devs}")
        split += shards[0].data.shape != leaf.shape
        alone += [key] if devs == [0] else []
    OUT.mkdir(exist_ok=True)
    listing = OUT / f"shards-{name.replace(' ', '-').replace(',', 'x')}.txt"
    listing.write_text("\n".join(lines) + "\n")
    log(f"[{name}] {len(lines)} parameter leaves: {split} split over the "
        f"mesh, {len(lines) - split} replicated, {len(alone)} on device 0 "
        f"alone (full list: {listing.relative_to(ROOT)})")
    for ln in lines[:4]:
        log(f"[{name}]   {ln}")
    check(not alone, f"{name}: {len(alone)} leaves on device 0 alone, "
                     f"e.g. {alone[:3]}")
    return placed


def count_equal(got, want) -> int:
    """Rows of ``got`` bit-identical to the same rows of ``want``."""
    return int(sum(np.array_equal(a, b) for a, b in zip(got, want)))


def mesh_phases(enc_cfg, dec_cfg, seed: int) -> None:
    """Meshed serving against unmeshed serving of the same requests.

    On the data-parallel mesh 4,1 every device runs the unmeshed program
    on its share of the batch, so the encoder must be bit-identical to
    unmeshed 2-row micro-batches (8 rows over 4 devices), and decode to
    unmeshed 1-slot decode (4 slots over 4 devices): the same tokens, and
    the same logits behind each. On 1,4 the fused backend declines every
    op and the reference path serves, partitioned by the compiler; it is
    held to the int8-vs-float bound against unmeshed reference serving."""
    from repro.launch.mesh import make_serving_mesh
    from repro.launch.serve import build_model
    (fparams, fplan), (params, plan) = build_encoder(enc_cfg, seed)
    reqs = encoder_requests(enc_cfg, seed)

    name = "encoder mesh 4,1"
    mesh = make_serving_mesh("4,1")
    placed = place_params(name, enc_cfg, mesh, params)
    got, eng, secs = serve_encoder(enc_cfg, placed, plan, reqs,
                                   backend="fused", mesh=mesh)
    log(f"[{name}] backend {eng.runtime.backend.describe()}, {secs:.1f}s "
        f"with compiles")
    kernel_census(name, eng.runtime, need_kernels=True)
    per_dev, _, _ = serve_encoder(enc_cfg, params, plan, reqs,
                                  backend="fused", max_batch=2)
    logit_gap(f"{name} vs unmeshed, 2-row micro-batches", got, per_dev)
    same = count_equal(got, per_dev)
    log(f"[{name}] bit-identical to unmeshed 2-row micro-batches on "
        f"{same}/{len(reqs)} requests")
    check(same == len(reqs), f"{name}: {len(reqs) - same} requests differ "
                             f"from unmeshed serving at the per-device batch")

    name = "encoder mesh 1,4"
    ref, _, _ = serve_encoder(enc_cfg, params, plan, reqs,
                              backend="reference")
    flt, _, _ = serve_encoder(enc_cfg, fparams, fplan, reqs,
                              backend="reference")
    bound = logit_gap("encoder unmeshed int8 reference vs float", ref, flt)
    mesh = make_serving_mesh("1,4")
    placed = place_params(name, enc_cfg, mesh, params)
    got, eng, secs = serve_encoder(enc_cfg, placed, plan, reqs,
                                   backend="fused", mesh=mesh)
    log(f"[{name}] backend {eng.runtime.backend.describe()}, {secs:.1f}s "
        f"with compiles")
    kernel_census(name, eng.runtime, need_kernels=False)
    logit_gap(f"{name} vs unmeshed reference", got, ref, bound)

    params, plan, _ = build_model(dec_cfg, "ffn", seed=seed, log=log)
    name = "decode mesh 4,1"
    mesh = make_serving_mesh("4,1")
    placed = place_params(name, dec_cfg, mesh, params)
    t0 = time.perf_counter()
    meshed, reqs = decode_engine(dec_cfg, placed, plan, seed,
                                 backend="fused", mesh=mesh)
    got = decode_rows(meshed)
    log(f"[{name}] backend {meshed.runtime.backend.describe()}, "
        f"{meshed.stats['ticks']} ticks, {time.perf_counter() - t0:.1f}s "
        f"with compiles")
    kernel_census(name, meshed.runtime, need_kernels=True)
    # each device serves 1 of the 4 slots with the unmeshed 1-slot program
    one, one_reqs = decode_engine(dec_cfg, params, plan, seed,
                                  backend="fused", slots=1)
    want = decode_rows(one)
    toks = {r.uid: list(r.output) for r in reqs}
    toks1 = {r.uid: list(r.output) for r in one_reqs}
    check(all(len(t) == NEW_TOKENS for t in toks.values()),
          f"{name}: not every request produced {NEW_TOKENS} tokens")
    same_tok = sum(toks[u] == toks1[u] for u in toks1)
    same_rows = sum(np.array_equal(got.get(k), v) for k, v in want.items())
    log(f"[{name}] vs unmeshed 1-slot decode: greedy tokens equal on "
        f"{same_tok}/{len(toks1)} requests, logits bit-identical on "
        f"{same_rows}/{len(want)} rows")
    check(same_tok == len(toks1) and same_rows == len(want) == len(got),
          f"{name}: meshed decode differs from unmeshed decode at the "
          f"per-device slot count")


# ---------------------------------------------------------------------------


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: encoder + decode phases on one chip; 4: only "
                         "the meshed phases, on four")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    src = ROOT / "src"
    if not (src / "repro").is_dir():
        print(f"chip_smoke: no repro package under {src}; run it from a "
              f"checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, str(src))

    import jax
    devices = jax.devices()
    dev = devices[0]
    log(f"[device] platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devices)}")
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, found {dev.platform!r}",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, found {len(devices)}", file=sys.stderr)
        return 1

    from repro.configs import get_config
    from repro.launch.cli import enable_compilation_cache
    log(f"[setup] compilation cache: {enable_compilation_cache()}")
    bert, qwen = get_config("bert-base"), get_config("qwen2-0.5b")
    t0 = time.perf_counter()
    # both sides of every comparison run their float matmuls at full f32
    # precision, so what differs is the implementation, not the precision
    with jax.default_matmul_precision("highest"):
        if args.chips == 4:
            mesh_phases(bert, qwen, args.seed)
        else:
            encoder_phase(bert, args.seed)
            decode_phase(qwen, args.seed)
    log(f"[done] all phases passed in {time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except PhaseError as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        sys.exit(1)
