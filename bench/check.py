"""The comparison that decides ``correct``.

After the window has closed and the program's state is freed, a sample of
the window's finished requests, drawn from the seed and holding the
longest, is run through the configuration's plain float32 reference on
the benchmark's own float weights. What is compared is what the timed path
returned, at the sizes it served:

* encoder cells (``logit_err``): the served logits of each sampled request
  against the reference's, as the largest absolute difference over the
  largest absolute reference logit of the sample;
* decode cells (``token_gap``): for every token the engine served, the gap
  by which the reference's logit of that token lies below the reference's
  best logit at that position, in units of the standard deviation of the
  reference's logits there; the widest gap of the sample. Greedy tokens
  only.

Besides, every float leaf of the parameter tree the window served from
has to be stored in float32, as the configuration states: the comparison
cannot tell float parts kept in bfloat16 from float32 ones, because the
int8 GEMMs' rounding is the larger (PERF.md).

``control`` puts the reference, at a precision below what the
configuration states, in the program's place and reads the same number from
it: ``"int4"`` (the plan's int8 GEMMs in int4) or ``"bf16"`` (the parts the
plan keeps in float32 in bfloat16, and all else with them); for decode, at
each position of the same histories, the gap of the token the control puts
first.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

#: served tokens and requests a decode sample holds at least (with the
#: longest request)
DECODE_SAMPLE_TOKENS, DECODE_SAMPLE_REQUESTS = 300, 4
#: requests an encoder sample holds at most
ENCODER_SAMPLE = 48
REF_BATCH = 16


def sample(records, seed: int, size_of, enough) -> list:
    """The longest finished request, then others in an order drawn from
    the seed, until ``enough(chosen)``."""
    ok = [r for r in records if r.ok]
    if not ok:
        return []
    longest = max(ok, key=size_of)
    rest = [r for r in ok if r is not longest]
    order = np.random.default_rng(seed ^ 0x5EED).permutation(len(rest))
    chosen = [longest]
    for i in order:
        if enough(chosen):
            break
        chosen.append(rest[i])
    return chosen


def encoder_number(ref, params, config, records, seed: int, max_len: int,
                   control=None) -> dict:
    chosen = sample(records, seed, lambda r: len(r.item.tokens),
                    lambda c: len(c) >= ENCODER_SAMPLE)
    heads = config["num_attention_heads"]
    kv_heads = config.get("num_key_value_heads", heads)
    fn = jax.jit(lambda p, t, s, n, c: ref.logits(
        p, t, s, n, heads=heads, kv_heads=kv_heads, control=c),
        static_argnums=(4,))
    worst, scale = 0.0, 0.0
    for k in range(0, len(chosen), REF_BATCH):
        part = chosen[k:k + REF_BATCH]
        toks = np.zeros((REF_BATCH, max_len), np.int32)
        segs = np.zeros((REF_BATCH, max_len), np.int32)
        lens = np.ones((REF_BATCH,), np.int32)
        for j, r in enumerate(part):
            n = len(r.item.tokens)
            toks[j, :n] = r.item.tokens
            if r.item.segments is not None:
                segs[j, :n] = r.item.segments
            lens[j] = n
        want = np.asarray(fn(params, toks, segs, lens, None),
                          np.float64)[:len(part)]
        if control:
            got = np.asarray(fn(params, toks, segs, lens, control),
                             np.float64)[:len(part)]
        else:
            got = np.stack([np.asarray(r.req.logits, np.float64)
                            for r in part])
        if not np.isfinite(got).all():
            return {"value": float("inf"), "requests": len(chosen)}
        worst = max(worst, float(np.abs(got - want).max()))
        scale = max(scale, float(np.abs(want).max()))
    return {"value": worst / scale if chosen else float("nan"),
            "requests": len(chosen)}


def decode_number(ref, params, config, records, seed: int, max_len: int,
                  control=None, rows_pad: int = 0) -> dict:
    """``rows_pad``: the mix's longest output, so that every run compiles
    one reference program."""
    chosen = sample(records, seed,
                    lambda r: len(r.item.tokens) + len(r.req.output),
                    lambda c: len(c) >= DECODE_SAMPLE_REQUESTS and
                    sum(len(r.req.output) for r in c) >= DECODE_SAMPLE_TOKENS)
    rows_max = max([rows_pad] + [len(r.req.output) for r in chosen])
    kw = dict(heads=config["num_attention_heads"],
              kv_heads=config["num_key_value_heads"])

    @jax.jit
    def gaps(p, toks, rows, picks_given, use_given):
        logits = ref.logits_at(p, toks, rows, **kw)
        ctrl = ref.logits_at(p, toks, rows, control=control, **kw) \
            if control else logits
        picks = jnp.where(use_given, picks_given, jnp.argmax(ctrl, -1))
        best = jnp.max(logits, -1)
        got = jnp.take_along_axis(logits, picks[:, None], -1)[:, 0]
        return (best - got) / jnp.std(logits, -1)

    worst, served = 0.0, 0
    for r in chosen:
        history = list(r.item.tokens) + list(r.req.output)
        toks = np.zeros((max_len,), np.int32)
        toks[:len(history)] = history
        n_out = len(r.req.output)
        rows = np.zeros((rows_max,), np.int32)
        rows[:n_out] = len(r.item.tokens) - 1 + np.arange(n_out)
        picks = np.zeros((rows_max,), np.int32)
        picks[:n_out] = r.req.output
        g = np.asarray(gaps(params, toks, rows, picks, control is None))[
            :n_out]
        if not np.isfinite(g).all():
            return {"value": float("inf"), "tokens": served}
        worst = max(worst, float(g.max()))
        served += n_out
    return {"value": worst if chosen else float("nan"), "tokens": served,
            "requests": len(chosen)}


def narrow_floats(params) -> int:
    """Float leaves of the served parameter tree stored narrower than the
    float32 that the configuration states (its ``compute_dtype``)."""
    return sum(1 for a in jax.tree_util.tree_leaves(params)
               if jnp.issubdtype(a.dtype, jnp.floating)
               and a.dtype.itemsize < 4)


def number(kind: str, *args, **kw) -> dict:
    return (encoder_number if kind == "encoder" else decode_number)(
        *args, **kw)
