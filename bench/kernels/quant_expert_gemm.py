"""Operations and bytes of one ``quant_expert_gemm`` call: the grouped W8A8
GEMM of the held experts' int8 (rows, k) buffers by their int8 (k, n)
weights, one call per expert GEMM of an MoE layer.

Counted as the work the algorithm needs for the rows routed to the held
experts: 2 * rows * k * n int8 operations, with ``rows`` the picks routed
to them in all; every held expert's int8 weight tile read once (the
weights stream whatever the rows), the routed rows' int8 activations read
once and their outputs written once in the output's dtype. Scales (O(n)
per expert) are left out.
"""
PEAK = "int8_ops_per_s"


def ops(rows: float, k: int, n: int) -> float:
    return 2 * rows * k * n


def bytes_moved(experts: int, rows: float, k: int, n: int,
                out_bytes: int) -> float:
    return experts * k * n + rows * k + rows * n * out_bytes
