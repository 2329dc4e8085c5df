"""``quant_expert_gemm``'s share of its roofline over the traced window, in
percent: each call's least time (``bench/kernels/quant_expert_gemm.py``)
at the chip's peaks, over the device time of its trace events. The rows a
call computes for routed picks are the counters' mean: the held experts'
buffer rows of the call times the window's ``moe_routed_rows`` over
``moe_expert_rows``. A program without the kernel or the counters gives
None."""
import readers
import spec
import tracereduce


def read(run):
    before, after = run.window.counters["before"], run.window.counters["after"]
    if "moe_expert_rows" not in after:
        return None
    rows_run = after["moe_expert_rows"] - before["moe_expert_rows"]
    if not rows_run:
        return None
    fill = (after["moe_routed_rows"] - before["moe_routed_rows"]) / rows_run
    qe, p = spec.kernel_counts("quant_expert_gemm"), run.peaks

    def least(op):
        (rt, (experts, m, n)), (_, (_, _, k)) = tracereduce.shapes(
            op.name)[:2]
        rows = fill * experts * m
        return (qe.ops(rows, k, n) / p[qe.PEAK],
                qe.bytes_moved(experts, rows, k, n,
                               tracereduce.DTYPE_BYTES[rt])
                / p["hbm_bytes_per_s"])
    return readers.roofline(run, "quant_expert_gemm", least)
