"""The MoE cell's yardstick: the grouped expert kernel's and the MoE
model's counts pinned to hand counts at the configuration's shapes, and its
three readers on hand-made and CPU-rehearsed windows."""
import math

import jax
import pytest

import drive
import moeops
import run
import spec
import system
import tracereduce
import traffic as traffic_mod
from reduced_cells import reduced_cell

CELL = "deepseek-v2-lite-ep4-samp.moe-chat"
CONFIG = spec.load_json(spec.BENCH / "configs"
                        / "deepseek-v2-lite-ep4-samp.json")
SEED = 2 ** 31 + 1616
READERS = ("moe.expert_gemm_roofline", "moe.expert_fill", "moe.mfu")


def test_quant_expert_gemm_counts_at_the_configs_shape():
    qe = spec.kernel_counts("quant_expert_gemm")
    # the gate projection of 16 held experts, 384 routed rows (64 slots x
    # 6 picks), d_model 2048 by expert width 1408, float32 out
    assert qe.ops(384, 2048, 1408) == 2_214_592_512
    assert qe.bytes_moved(16, 384, 2048, 1408, 4) == (
        16 * 2048 * 1408 + 384 * 2048 + 384 * 1408 * 4)


def test_model_ops_of_a_deepseek_token():
    i8, other = moeops.decode_token(CONFIG, 99)
    # dense SwiGLU 10944 wide; 8 layers of 2 shared experts (2816 wide);
    # 8 layers of 6 picks x 16/64 held, 1408 wide
    assert i8 == 134_479_872 + 276_824_064 + 207_618_048
    # per layer: wq 12.58M, kv_a 2.36M, kv_b 4.19M, attention over 100
    # keys 1.02M, wo 8.39M (28,549,120); router 8 x 262,144; head 419.4M
    assert other == 9 * 28_549_120 + 2_097_152 + 419_430_400


@pytest.mark.parametrize("name", READERS)
def test_each_metric_is_read_in_the_new_cell_only(name):
    (entry,) = [m for m in spec.benchmark()["per_layer"]
                if m["name"] == name]
    assert entry["workloads"] == [CELL]
    assert callable(spec.layer_reader(name).read)


class _Run:
    def __init__(self, trace, window, peaks=None, config=CONFIG):
        self.trace, self.window, self.peaks = trace, window, peaks
        self.config = config

    def steps_in_trace(self):
        return self.window.steps


def _window(counters):
    return drive.Window(1.0, [], [(0.0, 0.025, [99] * 64)],
                        counters={"before": {k: 0 for k in counters},
                                  "after": counters})


@pytest.mark.parametrize("name", READERS[:2])
def test_counter_readers_give_none_for_a_program_without_counters(name):
    parent = _window({"traces": 0, "real_tokens": 0, "padded_tokens": 0})
    assert spec.layer_reader(name).read(_Run(None, parent)) is None


def test_expert_gemm_roofline_on_a_hand_made_trace():
    """Two calls of 100 us on 16 held experts' 64-row buffers, half of the
    rows routed: the least time is the weight tile's at HBM bandwidth."""
    peaks = spec.peaks("TPU v5 lite")
    name = ("%quant_expert_gemm.3 = f32[16,64,1408]{2,1,0} custom-call("
            "s8[16,64,2048]{2,1,0} %a, s8[16,2048,1408]{2,1,0} %b, "
            "f32[16,1,1408]{2,1,0} %c), custom_call_target=\"tpu\"")
    ops = [tracereduce.Op(name, t, 100e-6, {}) for t in (0.0, 1e-3)]
    trace = tracereduce.Trace({"/device:TPU:0": ops}, [])
    window = _window({"moe_routed_rows": 512, "moe_expert_rows": 1024})
    got = spec.layer_reader("moe.expert_gemm_roofline").read(
        _Run(trace, window, peaks))
    rows = 0.5 * 16 * 64
    least = (16 * 2048 * 1408 + rows * 2048 + rows * 1408 * 4) \
        / peaks["hbm_bytes_per_s"]
    assert got == pytest.approx(100.0 * least / 100e-6, rel=1e-12)


def test_mfu_of_a_hand_made_tick():
    peaks = spec.peaks("TPU v5 lite")
    window = _window({})
    got = spec.layer_reader("moe.mfu").read(_Run(None, window, peaks))
    want = 64 * moeops.least_seconds(moeops.decode_token(CONFIG, 99), peaks)
    assert got == pytest.approx(100.0 * want / 0.025, rel=1e-12)


def test_expert_fill_reads_a_cpu_window(monkeypatch):
    """A one-second window of the cell at reduced widths, untraced: the
    held experts' buffers ran rows, and part of them held routed picks;
    the engine's queue wait (``dec.queue_ms``) reads there too."""
    assert jax.devices()[0].platform == "cpu"
    cell = reduced_cell(CELL, monkeypatch)
    sysm = system.build(cell, SEED, log=lambda *a: None)
    system.warm(sysm, log=lambda *a: None)
    items = traffic_mod.schedule(cell.traffic, 1.0, SEED,
                                 sysm.arch.vocab_size)
    win = drive.run(sysm, items, 1.0, cell.traffic["drain_s"])
    data = run.RunData(cell, win, None, None, sysm.max_len)
    value = spec.layer_reader("moe.expert_fill").read(data)
    assert value is not None and math.isfinite(value)
    assert 0.0 < value <= 100.0
    queue = spec.layer_reader("dec.queue_ms").read(data)
    assert queue is not None and math.isfinite(queue) and queue >= 0.0
