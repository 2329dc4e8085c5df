"""Operations and bytes of one ``quant_flash_attention`` call: the fully
int8 encoder attention core over b rows and h heads, sq queries by sk keys
of width hd.

Counted from the call's shapes as the work the algorithm needs: QK^T and
PV, 4 * b * h * sq * sk * hd int8 operations; int8 q, k and v read once,
the output written once in its dtype, the (b, sk) int32 key positions
read once.
"""
PEAK = "int8_ops_per_s"


def ops(b: int, h: int, sq: int, sk: int, hd: int) -> int:
    return 4 * b * h * sq * sk * hd


def bytes_moved(b: int, h: int, sq: int, sk: int, hd: int,
                out_bytes: int) -> int:
    return b * h * (sq + 2 * sk) * hd + b * h * sq * hd * out_bytes \
        + 4 * b * sk
