"""Operations and bytes of one ``decode_attention`` call: one query token
per slot over the slot's paged int8 keys and values, ``heads`` query heads
over ``kv_heads`` key/value heads of width hd.

Counted as the work the algorithm needs for the live context: with
``tokens`` the keys all live slots attend over in all, QK^T and PV take
4 * tokens * heads * hd operations (float, weighed at the bf16 peak); the
int8 keys and values of those tokens and their float32 per-token scales
are read once, and each live slot's float32 query read and output written
once.
"""
PEAK = "bf16_flops_per_s"


def ops(tokens: int, heads: int, hd: int) -> int:
    return 4 * tokens * heads * hd


def bytes_moved(tokens: int, slots: int, heads: int, kv_heads: int,
                hd: int) -> int:
    kv = 2 * tokens * kv_heads * (hd + 4)
    return kv + 2 * slots * heads * hd * 4
