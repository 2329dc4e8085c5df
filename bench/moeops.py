"""Model operations per decode token of a DeepSeek-V2-style model (latent
attention, a leading dense FFN, routed and shared experts), from a
configuration file's published sizes: the work the model needs, whatever
executes it. ``modelops.py`` counts a dense GQA model; this is its
counterpart for the MoE/MLA family.

Each count is split into the operations of the GEMMs that the
configuration's plan runs in int8 (``int8_gemms``: ``ffn.*`` the dense
FFN, ``experts.*`` the routed experts, ``shared.*`` the shared experts)
and all others. An operation is a multiply or an add: a matrix product of
(m, k) by (k, n) is 2mkn.

Attention is counted in its expanded form: each head's query of
``qk_nope_head_dim + qk_rope_head_dim`` dims against ``pos + 1`` keys, and
as many weights over values of ``v_head_dim``; the latent's expansion to
the token's own keys and values is one GEMM. The routed experts of a token
are its ``num_experts_per_tok`` picks times the share of the experts this
chip holds (``n_routed_experts`` over ``published.n_routed_experts``): the
picks that land here on average under uniform routing.
"""
from __future__ import annotations

import modelops


def _sizes(c: dict) -> dict:
    held = c["n_routed_experts"]
    total = c.get("published", {}).get("n_routed_experts", held)
    return dict(d=c["hidden_size"], h=c["num_attention_heads"],
                r=c["kv_lora_rank"], nope=c["qk_nope_head_dim"],
                rope=c["qk_rope_head_dim"], v=c["v_head_dim"],
                ff=c["intermediate_size"], fe=c["moe_intermediate_size"],
                shared=c["n_shared_experts"], k=c["num_experts_per_tok"],
                experts=total, share=held / total,
                layers=c["num_hidden_layers"],
                dense=c["first_k_dense_replace"], vocab=c["vocab_size"])


def _glu(d: int, ff: int) -> int:
    """One token through a SwiGLU FFN of width ``ff``."""
    return 3 * 2 * d * ff


def decode_token(c: dict, pos: int) -> tuple:
    """(int8, other) operations of one decode-step token at position
    ``pos`` (it attends over ``pos + 1`` keys), LM head included."""
    s = _sizes(c)
    q8 = {name.split(".")[0] for name in c.get("int8_gemms", ())}
    d, h = s["d"], s["h"]
    qk = s["nope"] + s["rope"]
    attn = (2 * d * h * qk                          # query projection
            + 2 * d * (s["r"] + s["rope"])          # latent and rope key
            + 2 * s["r"] * h * (s["nope"] + s["v"])  # latent -> keys, values
            + 2 * (pos + 1) * h * (qk + s["v"])     # QK^T and PV
            + 2 * h * s["v"] * d)                   # output projection
    moe_layers = s["layers"] - s["dense"]
    parts = {"ffn": s["dense"] * _glu(d, s["ff"]),
             "shared": moe_layers * _glu(d, s["shared"] * s["fe"]),
             "experts": moe_layers * s["k"] * s["share"] * _glu(d, s["fe"])}
    i8 = sum(v for k, v in parts.items() if k in q8)
    other = sum(v for k, v in parts.items() if k not in q8)
    other += s["layers"] * attn + moe_layers * 2 * d * s["experts"]  # router
    other += 2 * d * s["vocab"]                     # LM head
    return i8, other


def least_seconds(ops: tuple, peaks: dict) -> float:
    return modelops.least_seconds(ops, peaks)
