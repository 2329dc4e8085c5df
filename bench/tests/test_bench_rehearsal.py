"""The harness rehearsed on the CPU: a one-second window of every cell's
traffic through the harness's own functions, at reduced() widths with the
kernels in interpret mode."""
import json
import os
import subprocess
import sys

import jax
import pytest

import run
import spec
import traffic as traffic_mod
from reduced_cells import reduced_cell

CELLS = [w["name"] for w in spec.benchmark()["workloads"]]
SEED = 2 ** 31 + 12345                  # beyond 32 signed bits
CONTRACT_KEYS = ["correct", "attempted", "failed", "metrics", "device",
                 "checks"]


@pytest.mark.parametrize("name", CELLS)
def test_schedule_is_the_seeds_and_keeps_its_sizes(name):
    cell = spec.cell(name)
    vocab = cell.config["vocab_size"]
    a = traffic_mod.schedule(cell.traffic, 10.0, SEED, vocab)
    b = traffic_mod.schedule(cell.traffic, 10.0, SEED, vocab)
    c = traffic_mod.schedule(cell.traffic, 10.0, SEED + 1, vocab)
    assert a == b
    assert a != c
    # another seed offers the same sizes and arrivals, in another order
    assert len(a) == len(c) > 0
    assert sorted(len(x.tokens) for x in a) == sorted(len(x.tokens)
                                                      for x in c)
    assert sorted(x.max_tokens for x in a) == sorted(x.max_tokens for x in c)
    assert abs(a[-1].due - c[-1].due) < 1e-9
    assert all(0 <= x.due < 10.0 for x in a)


@pytest.mark.parametrize("name", CELLS)
def test_one_second_window_on_the_cpu(name, monkeypatch):
    cell = reduced_cell(name, monkeypatch)
    result = run.execute(cell, SEED, 1.0, False, jax.devices())
    line = json.loads(json.dumps(result))
    assert list(line) == CONTRACT_KEYS
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["checks"]["compiles"]["value"] == 0
    want = {m["name"] for m in cell.end_to_end}
    assert set(line["metrics"]) == want
    assert all(m["value"] > 0 for m in line["metrics"].values())
    name_ = cell.config["check"]["name"]
    assert line["checks"][name_]["value"] <= line["checks"][name_]["limit"]
    assert line["correct"] is True


def test_run_exits_non_zero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(spec.BENCH / "run.py"), "--workload", CELLS[0],
         "--seed", str(SEED), "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr
