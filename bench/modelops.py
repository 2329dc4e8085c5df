"""Model operations per request or per token, from a configuration file's
published sizes: the work the model needs, whatever executes it.

Each count is split into the operations of the GEMMs (and attention
products) that the configuration's plan runs in int8, and all others. An
operation is a multiply or an add: a matrix product of (m, k) by (k, n)
is 2mkn.
"""
from __future__ import annotations


def _sizes(c: dict) -> dict:
    d = c["hidden_size"]
    h = c["num_attention_heads"]
    hd = d // h
    kv = c.get("num_key_value_heads", h)
    return dict(d=d, h=h, hd=hd, q=h * hd, kv=kv * hd,
                ff=c["intermediate_size"], layers=c["num_hidden_layers"])


def gemms(c: dict) -> dict:
    """(k, n) of each block GEMM of one layer, by its name in the plan."""
    s = _sizes(c)
    out = {"attn.wq": (s["d"], s["q"]), "attn.wk": (s["d"], s["kv"]),
           "attn.wv": (s["d"], s["kv"]), "attn.wo": (s["q"], s["d"])}
    if c.get("hidden_act") == "silu":              # gated (SwiGLU) FFN
        out.update({"ffn.wg": (s["d"], s["ff"]), "ffn.wu": (s["d"], s["ff"]),
                    "ffn.wd": (s["ff"], s["d"])})
    else:
        out.update({"ffn.wi": (s["d"], s["ff"]), "ffn.wo": (s["ff"], s["d"])})
    return out


def _layers(c: dict, rows: int, attn_pairs: int) -> tuple:
    """(int8, other) operations of every layer for ``rows`` tokens whose
    attention spans ``attn_pairs`` (query, key) pairs in all."""
    s = _sizes(c)
    q8 = set(c.get("int8_gemms", ()))
    i8 = other = 0
    for name, (k, n) in gemms(c).items():
        ops = 2 * rows * k * n
        if name in q8:
            i8 += ops
        else:
            other += ops
    attn = 2 * 2 * attn_pairs * s["h"] * s["hd"]        # QK^T and PV
    if c.get("int8_attention"):
        i8 += attn
    else:
        other += attn
    return i8 * s["layers"], other * s["layers"]


def encoder_request(c: dict, n: int) -> tuple:
    """(int8, other) operations to classify one request of ``n`` tokens:
    every layer over the ``n`` tokens, then the pooler and the classes at
    the CLS position."""
    i8, other = _layers(c, n, n * n)
    d = c["hidden_size"]
    other += 2 * d * d + 2 * d * int(c["head"][1])
    return i8, other


def decode_token(c: dict, pos: int) -> tuple:
    """(int8, other) operations of one decode-step token at position
    ``pos`` (it attends over ``pos + 1`` keys), LM head included."""
    i8, other = _layers(c, 1, pos + 1)
    other += 2 * c["hidden_size"] * c["vocab_size"]
    return i8, other


def least_seconds(ops: tuple, peaks: dict) -> float:
    """Time at peak: int8 operations at the int8 peak, others at bf16's."""
    i8, other = ops
    return i8 / peaks["int8_ops_per_s"] + other / peaks["bf16_flops_per_s"]
