"""The readers of the program's own phases and queue waits
(``bench/hostphases.py``): on the pinned TPU trace, which predates the
program's ``samp.`` spans; on a window shaped as a program without the
counters leaves it; on hand-made spans; and on a one-second window of each
cell rehearsed on the CPU."""
import math

import jax
import pytest

import drive
import hostphases
import run
import spec
import system
import tracereduce
import traffic as traffic_mod
from reduced_cells import reduced_cell

DATA = spec.BENCH / "tests" / "data" / "decode_ticks.xplane.pb"
SEED = 2 ** 31 + 4242
ENC, DEC = "bert-base-samp.clue-mix", "qwen2-0.5b-samp.chat-decode"
TRACE_READERS = {"enc.host_bound_share": ENC, "dec.host_bound_share": DEC}
COUNTER_READERS = {"enc.batch_wait_ms": ENC, "enc.host_ms": ENC,
                   "enc.fetch_ms": ENC, "dec.admit_wait_ms": DEC,
                   "dec.host_ms": DEC, "dec.fetch_ms": DEC}
NEW = {**TRACE_READERS, **COUNTER_READERS}


class _Run:
    def __init__(self, trace, window):
        self.trace, self.window = trace, window


@pytest.mark.parametrize("name", sorted(NEW))
def test_each_new_metric_is_read_in_its_one_cell(name):
    (entry,) = [m for m in spec.benchmark()["per_layer"]
                if m["name"] == name]
    assert entry["workloads"] == [NEW[name]]
    assert callable(spec.layer_reader(name).read)


@pytest.mark.parametrize("engine", ["enc", "dec"])
def test_trace_readers_find_no_program_spans_in_the_pinned_trace(engine):
    trace = tracereduce.load(DATA)
    window = drive.Window(1.0, [], [], trace_at=(0.0, 0.08))
    assert hostphases.samp_spans(DATA) == []
    assert hostphases.host_bound_share(_Run(trace, window), engine,
                                       path=DATA) is None


@pytest.mark.parametrize("name", sorted(TRACE_READERS))
def test_trace_readers_without_a_profile_read_nothing(name):
    window = drive.Window(1.0, [], [])
    assert spec.layer_reader(name).read(_Run(None, window)) is None


def test_host_bound_seconds_are_idle_time_in_host_phases():
    ops = [tracereduce.Op("a", s, 1.0, {}) for s in (0.0, 2.0, 4.0, 6.0)]
    spans = [("samp.dec.tick", 0.5, 6.5),
             ("samp.dec.sample", 1.2, 1.8),      # gap 1-2: host work
             ("samp.dec.fetch", 3.2, 3.9),       # gap 3-4: 0.7 s copying
             ("samp.enc.step", 4.9, 5.9)]        # gap 5-6: another engine
    devices = {"/device:TPU:0": ops}
    # the three gaps, less the copy's 0.7 s
    assert hostphases.host_bound_seconds(devices, spans, "dec") == \
        pytest.approx(2.3)
    assert hostphases.host_bound_seconds(devices, spans, "enc") == \
        pytest.approx(0.9)
    assert hostphases.host_bound_seconds(devices, spans[:1], "enc") is None
    # a span that reaches past the last operation adds nothing
    assert hostphases.host_bound_seconds(
        devices, [("samp.enc.step", 6.5, 9.0)], "enc") == 0.0


def test_coverage_log_names_idle_outside_the_program(capsys):
    ops = [tracereduce.Op("a", s, 1.0, {}) for s in (0.0, 2.0, 4.0)]
    bench = [("wait", 0.9, 2.1), ("admit", 3.0, 4.0)]
    spans = [("samp.enc.step", 3.1, 3.3)]
    hostphases._log_coverage(
        tracereduce.Trace({"/device:TPU:0": ops}, bench), spans)
    err = capsys.readouterr().err
    assert "idle under bench.wait: 1.000000 s" in err
    assert "idle under bench.admit: 1.000000 s" in err
    assert "2.000000 s, 50.00% of it outside" in err


def _parent_window() -> drive.Window:
    """What a program without phase counters or request stamps leaves."""
    class Req:                                   # no ``step``, no waits
        pass
    item = traffic_mod.Item(uid=0, due=0.0, tokens=[1, 2])
    rec = drive.Record(item, submitted=0.0, done=0.5, req=Req())
    counters = {"traces": 0, "real_tokens": 2, "padded_tokens": 6}
    return drive.Window(1.0, [rec], [(0.0, 0.5, [2])],
                        counters={"before": dict(counters),
                                  "after": dict(counters)})


@pytest.mark.parametrize("name", sorted(COUNTER_READERS))
def test_counter_readers_give_none_for_a_program_without_counters(name):
    assert spec.layer_reader(name).read(_Run(None, _parent_window())) is None


@pytest.fixture(scope="module")
def windows():
    """A one-second window of each cell at reduced widths, untraced."""
    out = {}
    with pytest.MonkeyPatch.context() as mp:
        for name in (ENC, DEC):
            cell = reduced_cell(name, mp)
            sysm = system.build(cell, SEED, log=lambda *a: None)
            system.warm(sysm, log=lambda *a: None)
            items = traffic_mod.schedule(cell.traffic, 1.0, SEED,
                                         sysm.arch.vocab_size)
            win = drive.run(sysm, items, 1.0, cell.traffic["drain_s"])
            out[name] = run.RunData(cell, win, None, None, sysm.max_len)
    return out


@pytest.mark.parametrize("name", sorted(COUNTER_READERS))
def test_counter_readers_read_a_cpu_window(name, windows):
    assert jax.devices()[0].platform == "cpu"
    value = spec.layer_reader(name).read(windows[COUNTER_READERS[name]])
    assert value is not None and math.isfinite(value) and value >= 0.0
    if not name.endswith("wait_ms"):     # a queue may be empty, hosts work
        assert value > 0.0
