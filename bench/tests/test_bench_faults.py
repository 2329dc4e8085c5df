"""The comparison that decides ``correct`` must fail what is wrong.

A run is driven on the CPU at reduced() widths (the harness's look for a
chip skipped) with the timed path broken underneath, once for each fault a
serving cell can have, and ``correct`` must come out false; float
weights kept in bfloat16 must too. The control
(the reference with its int8 GEMMs in int4, in the program's place) must
read above the cell's limit while the sound program reads under it.
"""
import gc

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import drive
import run
import system
import traffic as traffic_mod
from reduced_cells import reduced_cell
from repro.serve.runtime import Runtime

SEED = 2 ** 31 + 777
ENCODER = "bert-base-samp.clue-mix"
DECODE = "qwen2-0.5b-samp.chat-decode"


def _answers_altered(monkeypatch):
    """Every encoder answer comes back with its classes in reverse order."""
    inner = Runtime.encode

    def encode(self, params, inputs, lengths=None):
        return np.asarray(inner(self, params, inputs, lengths))[..., ::-1]
    monkeypatch.setattr(Runtime, "encode", encode)


def _decode_fault(monkeypatch, fault):
    inner = Runtime.decode_fn

    def decode_fn(self, params, caches):
        step = inner(self, params, caches)

        def broken(params, caches, *args, **kw):
            logits, new = step(params, caches, *args, **kw)
            if fault == "token":          # the produced token is the next id
                return jnp.roll(logits, 1, axis=-1), new
            return logits, caches         # "state": caches come back as given
        return broken
    monkeypatch.setattr(Runtime, "decode_fn", decode_fn)


def _float_parts_in_bf16(monkeypatch):
    """The served model keeps its float weight tables in bfloat16."""
    inner = system.quantize

    def quantize(config, arch, params, seed):
        qparams, plan, precision = inner(config, arch, params, seed)
        qparams = jax.tree_util.tree_map(
            lambda a: a.astype(jnp.bfloat16)
            if a.dtype == jnp.float32 and a.ndim >= 2 else a, qparams)
        return qparams, plan, precision
    monkeypatch.setattr(system, "quantize", quantize)


@pytest.mark.parametrize("name,fault", [(ENCODER, "answer"),
                                        (DECODE, "token"),
                                        (DECODE, "state"),
                                        (ENCODER, "bf16"),
                                        (DECODE, "bf16")])
def test_a_broken_timed_path_is_not_correct(name, fault, monkeypatch):
    cell = reduced_cell(name, monkeypatch)
    if fault == "answer":
        _answers_altered(monkeypatch)
    elif fault == "bf16":
        _float_parts_in_bf16(monkeypatch)
    else:
        _decode_fault(monkeypatch, fault)
    result = run.execute(cell, SEED, 1.0, False, jax.devices())
    check = result["checks"][
        "narrow_floats" if fault == "bf16" else cell.config["check"]["name"]]
    assert result["correct"] is False
    assert check["value"] > check["limit"]


@pytest.mark.parametrize("name", [ENCODER, DECODE])
def test_the_control_fails_the_limit(name, monkeypatch):
    cell = reduced_cell(name, monkeypatch)
    sysm = system.build(cell, SEED, log=lambda _m: None)
    system.warm(sysm, log=lambda _m: None)
    items = traffic_mod.schedule(cell.traffic, 1.0, SEED,
                                 sysm.arch.vocab_size)
    win = drive.run(sysm, items, 1.0, cell.traffic["drain_s"])
    max_len, arch = sysm.max_len, sysm.arch
    sysm.engine = None
    gc.collect()
    limit = cell.config["check"]["limit"]
    program = run.compare(cell, win.records, SEED, max_len, arch)["value"]
    control = run.compare(cell, win.records, SEED, max_len, arch,
                          control="int4")["value"]
    assert program <= limit < control
