"""Greedy tokens are picked on the device; logits reach the host only for
a slot that samples.

Each tick the engine takes the argmax of the step's logits on the device
and copies the (slots,) ids to the host; it copies the logits too only on
a tick where a live slot at its text frontier has ``temperature > 0``.
Served through a small paged pool that forces a preemption replay, every
token must equal the host's pick from that tick's logits row: the first
maximum for a greedy slot, and for a sampled slot the draw numpy makes
from the same row with the same generator in the same order.
"""
import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.plan import PrecisionPlan
from repro.models import transformer as T
from repro.serve import Request, ServeEngine

KEY = jax.random.PRNGKey(0)
PROMPTS = [[2, 17, 9], [5, 40], [11, 3, 7, 1], [23, 8]]
SEED = 2 ** 31 + 5


def _serve(name, kv_cache, temperatures):
    """Serve PROMPTS at these temperatures through a 4-page pool shared by
    two slots; returns (engine, requests, ticks), a tick being the live
    slots' (request, cursor) as the step starts and the logits it gave."""
    cfg = get_config(name).reduced()
    policy = PrecisionPlan.full_float(cfg.num_layers, "float32")
    params = T.init_params(KEY, cfg, policy)
    eng = ServeEngine(cfg, params, T.build_plan(cfg, policy), batch_slots=2,
                      max_len=64, page_size=4, pool_pages=4,
                      kv_cache=kv_cache, seed=SEED)
    inner, ticks = eng._decode, []

    def recording(params, caches, tokens, pos, active, pages=None):
        at = [(s, r, int(eng.sched.cursor[s]))
              for s, r in enumerate(eng.sched.active)
              if r is not None and active[s]]
        logits, *rest = inner(params, caches, tokens, pos, active, pages)
        ticks.append((at, np.asarray(logits, np.float32)))
        return (logits, *rest)
    eng._decode = recording
    reqs = [Request(uid=i, prompt=list(p), max_tokens=8, temperature=t)
            for i, (p, t) in enumerate(zip(PROMPTS, temperatures))]
    for r in reqs:
        eng.submit(r)
    eng.run()
    return eng, reqs, ticks


def _host_picks(ticks, seed):
    """Replay the recorded ticks on the host: (tokens by uid, ticks on
    which a sampled slot drew from the logits)."""
    rng = np.random.default_rng(seed)
    out: dict = {}
    draws = 0
    for at, logits in ticks:
        drew = False
        for s, req, c in at:
            mine = out.setdefault(req.uid, [])
            if c + 1 < len(req.prompt) + len(mine):
                continue                    # prompt, or a replayed token
            row = logits[s]
            if req.temperature > 0:
                p = np.exp((row - row.max()) / req.temperature)
                p /= p.sum()
                mine.append(int(rng.choice(len(p), p=p)))
                drew = True
            else:
                mine.append(int(row.argmax()))
        draws += drew
    return out, draws


@pytest.mark.parametrize("name,kv_cache,temperatures", [
    ("qwen2-0.5b", "int8_per_token", (0.0, 0.0, 0.0, 0.0)),
    ("mixtral-8x22b", "int8_per_token", (0.0, 0.0, 0.0, 0.0)),
    # MLA pages its latent in float
    ("deepseek-v2-lite-ep4", "float", (0.0, 0.0, 0.0, 0.0)),
    ("qwen2-0.5b", "int8_per_token", (0.0, 0.8, 0.0, 0.0)),
])
def test_served_tokens_are_the_host_picks(name, kv_cache, temperatures):
    eng, reqs, ticks = _serve(name, kv_cache, temperatures)
    assert eng.stats["preemptions"] > 0
    assert all(r.done and len(r.output) == 8 for r in reqs)
    want, draws = _host_picks(ticks, SEED)
    assert {r.uid: r.output for r in reqs} == want
    assert eng.stats["logits_fetches"] == draws
    if not any(temperatures):
        assert draws == 0
        return
    assert draws > 0
    # the greedy requests' tokens are those of an all-greedy run
    _, greedy, _ = _serve(name, kv_cache, (0.0,) * len(PROMPTS))
    for r, g, t in zip(reqs, greedy, temperatures):
        if t == 0:
            assert r.output == g.output, r.uid
