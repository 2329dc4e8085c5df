"""The reduction from a profiler trace to device metrics, pinned on a small
trace recorded on a TPU v5e: 80 ms of qwen2-0.5b-samp.chat-decode at 32
live slots (three decode ticks and the ends of two more), cut from a
``--trace 1`` run, with the benchmark's ``bench.step`` host spans."""
import pytest

import readers
import spec
import tracereduce

DATA = spec.BENCH / "tests" / "data" / "decode_ticks.xplane.pb"


@pytest.fixture(scope="module")
def trace():
    return tracereduce.load(DATA)


class _Run:
    """What a reader needs: the trace, the peaks, and the host's ticks (32
    live slots at positions 100..131)."""

    def __init__(self, trace):
        self.trace = trace
        self.peaks = spec.peaks("TPU v5 lite")

    def steps_in_trace(self):
        return [(0.0, 1.0, list(range(100, 132)))]


def test_devices_and_host_spans(trace):
    assert list(trace.devices) == ["/device:TPU:0"]
    assert len(trace.ops()) == 7233
    assert [s[0] for s in trace.spans] == ["step"] * 5


def test_busy_seconds_are_the_union_of_the_operations(trace):
    ops = trace.ops()
    span = max(o.end for o in ops) - min(o.start for o in ops)
    assert tracereduce.busy_seconds(trace) == pytest.approx(0.066354778,
                                                            rel=1e-9)
    assert span == pytest.approx(0.087861282, rel=1e-9)
    # the loop that holds each tick's layers is counted once, not on top of
    # the operations inside it
    assert sum(o.dur for o in ops) > span


def test_operation_seconds_leave_out_loops_and_group_by_name(trace):
    secs = tracereduce.op_seconds(trace)
    assert "while" not in secs
    top = tracereduce.top(secs, 3)
    assert [name for name, _ in top] == ["copy", "decode_attention",
                                        "quant_linear"]
    assert top[1][1] == pytest.approx(0.014554303, rel=1e-9)
    assert top[2][1] == pytest.approx(0.007838439, rel=1e-9)
    assert sum(secs.values()) <= tracereduce.busy_seconds(trace) + 1e-9


def test_kernel_events_and_their_shapes(trace):
    ql = tracereduce.kernel_ops(trace, "quant_linear")
    da = tracereduce.kernel_ops(trace, "decode_attention")
    assert (len(ql), len(da)) == (225, 75)
    assert tracereduce.shapes(ql[0].name)[:3] == [
        ("f32", (32, 4864)), ("s8", (32, 896)), ("s8", (896, 4864))]
    assert tracereduce.shapes(da[0].name)[0] == ("f32", (32, 2, 7, 64))


def test_idle_gaps_are_attributed_to_the_host_span_around_them(trace):
    idle = tracereduce.idle_by_activity(trace)
    assert list(idle) == ["step"]
    assert idle["step"] == pytest.approx(0.021506504, rel=1e-9)


def test_roofline_shares(trace):
    run = _Run(trace)
    assert readers.quant_linear_roofline(run) == pytest.approx(
        17.112175939, rel=1e-9)
    assert readers.decode_attention_roofline(run) == pytest.approx(
        0.782337267, rel=1e-9)
