"""Operations and bytes of one ``quant_linear`` call: the W8A8 GEMM of an
int8 (m, k) activation by an int8 (k, n) weight.

Counted from the call's shapes as the work the algorithm needs: 2mkn int8
operations; the int8 activation and weight read once and the output
written once in its dtype. Scales and biases (O(n)) are left out.
"""
PEAK = "int8_ops_per_s"


def ops(m: int, k: int, n: int) -> int:
    return 2 * m * k * n


def bytes_moved(m: int, k: int, n: int, out_bytes: int) -> int:
    return m * k + k * n + m * n * out_bytes
