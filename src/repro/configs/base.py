"""Architecture configuration schema + registry.

Every assigned architecture is an :class:`ArchConfig` instance in its own
module under ``repro/configs``; ``get_config(name)`` resolves by id. Each
config also provides a ``reduced()`` smoke-test variant (same family, tiny
dims) — the full configs are only ever lowered via the dry-run
(ShapeDtypeStruct, no allocation).
"""
from __future__ import annotations

import dataclasses
from typing import Optional


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    """A routed-expert FFN. ``router``: ``topk_softmax`` takes the top-k
    logits and a softmax over those k (Mixtral); ``softmax_topk`` takes a
    softmax over all experts and keeps the greedy top-k of those
    probabilities unrenormalized (DeepSeek-V2-Lite). The layer holds the
    first ``held_count`` of ``num_experts`` (one chip's expert-parallel
    share; None holds them all): the router keeps its ``num_experts``
    outputs, and a pick of an expert not held adds nothing.
    ``capacity_factor`` None is dropless: every pick of a held expert is
    computed."""
    num_experts: int
    top_k: int
    d_ff_expert: int
    num_shared: int = 0          # shared (always-on) experts, deepseek-v2: 2
    first_dense: int = 0         # leading dense-FFN layers, deepseek-v2: 1
    capacity_factor: Optional[float] = 1.25
    router: str = "topk_softmax"     # | "softmax_topk"
    held_count: Optional[int] = None

    def __post_init__(self):
        if self.router not in ("topk_softmax", "softmax_topk"):
            raise ValueError(f"unknown MoE router {self.router!r}")
        if not 0 < self.held <= self.num_experts:
            raise ValueError(f"{self.held} held experts of "
                             f"{self.num_experts}")

    @property
    def held(self) -> int:
        """How many experts, from expert 0, this layer holds."""
        return (self.num_experts if self.held_count is None
                else self.held_count)


@dataclasses.dataclass(frozen=True)
class RopeScaling:
    """YaRN rotary scaling (arXiv:2309.00071), as DeepSeek-V2 computes it:
    rotary frequency indices between the correction dims of ``beta_fast``
    and ``beta_slow`` rotations over ``original_max_position`` blend from
    the base frequencies to those divided by ``factor``, and the attention
    softmax scale is multiplied by ``yarn_mscale(factor, mscale) ** 2``.
    ``mscale`` stands for DeepSeek-V2's equal ``mscale`` and
    ``mscale_all_dim``, whose ratio scales cos and sin: by 1."""
    factor: float
    original_max_position: int
    beta_fast: float
    beta_slow: float
    mscale: float


@dataclasses.dataclass(frozen=True)
class MLAConfig:
    """Multi-head Latent Attention (deepseek-v2)."""
    kv_lora_rank: int            # 512
    q_lora_rank: int             # 1536 (0 = no q compression)
    qk_nope_dim: int             # 128
    qk_rope_dim: int             # 64
    v_head_dim: int              # 128


@dataclasses.dataclass(frozen=True)
class BlockKind:
    """Static identity of one layer's body; contiguous equal-kind runs share
    one lax.scan."""
    body: str                    # 'attn' | 'rglru' | 'mlstm' | 'slstm'
    local: bool = False          # sliding-window / local-attention mask
    moe: bool = False            # FFN group is a mixture-of-experts

    def __str__(self):
        tags = [self.body]
        if self.local:
            tags.append("local")
        if self.moe:
            tags.append("moe")
        return "+".join(tags)


@dataclasses.dataclass(frozen=True)
class ArchConfig:
    name: str
    family: str                  # dense|moe|vlm|ssm|audio|hybrid|bert
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    d_ff: int
    vocab_size: int
    # --- attention ---
    attention: str = "full"      # full|sliding|local_global|none
    sliding_window: int = 4096
    attn_softcap: Optional[float] = None
    final_softcap: Optional[float] = None
    qkv_bias: bool = False
    causal: bool = True          # False => encoder-only (bidirectional)
    mla: Optional[MLAConfig] = None
    moe: Optional[MoEConfig] = None
    # --- ffn / norms / positions ---
    ffn_kind: str = "glu"        # glu|gelu|none
    norm_kind: str = "rmsnorm"   # rmsnorm|layernorm
    position: str = "rope"       # rope|learned|none
    rope_theta: float = 10_000.0
    rope_scaling: Optional[RopeScaling] = None    # YaRN (deepseek-v2-lite)
    max_position: int = 524_288  # learned-position table size cap
    tie_embeddings: bool = True
    emb_scale_by_sqrt_dim: bool = False   # gemma family
    # --- hybrid / ssm block pattern (cycled over layers) ---
    pattern: tuple[str, ...] = ("attn",)
    # 'attn' | 'attn_local' | 'attn_global' | 'rglru' | 'mlstm' | 'slstm'
    # --- ssm extras ---
    rnn_width: int = 0           # RG-LRU recurrence width (0 => d_model)
    conv_width: int = 4          # temporal-conv window in recurrent blocks
    proj_factor: float = 2.0     # xLSTM block up-projection factor
    # --- modality frontend stubs ---
    frontend: Optional[str] = None        # 'vision'|'audio'|None
    num_prefix_embeds: int = 0            # e.g. 256 SigLIP patches
    frontend_dim: int = 0                 # raw frontend embedding width
    # --- bert extras ---
    num_segments: int = 0        # >0 => add segment embeddings (BERT)
    # --- capability flags (drive shape-cell skips; see DESIGN.md) ---
    supports_decode: bool = True
    subquadratic: bool = False   # may run long_500k

    # ------------------------------------------------------------------
    @property
    def q_dim(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.num_kv_heads * self.head_dim

    def layer_kinds(self) -> tuple[BlockKind, ...]:
        """Expand ``pattern`` over ``num_layers`` into per-layer BlockKinds,
        applying MoE placement (``moe.first_dense`` leading layers dense)."""
        kinds = []
        for i in range(self.num_layers):
            p = self.pattern[i % len(self.pattern)]
            if p in ("attn", "attn_global"):
                k = BlockKind("attn", local=False)
            elif p == "attn_local":
                k = BlockKind("attn", local=True)
            elif p in ("rglru", "mlstm", "slstm"):
                k = BlockKind(p)
            else:
                raise ValueError(f"unknown pattern entry {p!r}")
            if self.moe is not None and k.body == "attn":
                if i >= self.moe.first_dense:
                    k = dataclasses.replace(k, moe=True)
            kinds.append(k)
        return tuple(kinds)

    def replace(self, **kw) -> "ArchConfig":
        return dataclasses.replace(self, **kw)

    def reduced(self) -> "ArchConfig":
        """Smoke-test variant: same family/pattern semantics, tiny dims."""
        kw: dict = dict(
            num_layers=min(self.num_layers, 4 * max(1, len(self.pattern) // 2)),
            d_model=64,
            num_heads=4,
            num_kv_heads=min(self.num_kv_heads, 2) or 1,
            head_dim=16,
            d_ff=128 if self.d_ff else 0,
            vocab_size=128,
            sliding_window=8,
            max_position=512,
            rnn_width=64 if self.rnn_width else 0,
            num_prefix_embeds=4 if self.num_prefix_embeds else 0,
            frontend_dim=32 if self.frontend_dim else 0,
        )
        if self.moe is not None and self.moe.router == "softmax_topk":
            # keep the router's k and the held share's proportion: 8
            # experts, of which a held share scaled as published
            kw["moe"] = dataclasses.replace(
                self.moe, num_experts=8, d_ff_expert=32,
                top_k=min(self.moe.top_k, 8),
                num_shared=min(self.moe.num_shared, 1),
                first_dense=min(self.moe.first_dense, 1),
                held_count=(None if self.moe.held_count is None else max(
                    1, self.moe.held_count * 8 // self.moe.num_experts)))
        elif self.moe is not None:
            kw["moe"] = dataclasses.replace(
                self.moe, num_experts=4, top_k=2, d_ff_expert=32,
                num_shared=min(self.moe.num_shared, 1),
                first_dense=min(self.moe.first_dense, 1))
        if self.mla is not None:
            kw["mla"] = MLAConfig(kv_lora_rank=32,
                                  q_lora_rank=32 if self.mla.q_lora_rank
                                  else 0,
                                  qk_nope_dim=16, qk_rope_dim=8, v_head_dim=16)
        # keep the pattern length compatible with the reduced layer count
        n = kw["num_layers"]
        if len(self.pattern) > 1:
            n = max(n, len(self.pattern))
            n -= n % len(self.pattern)
            kw["num_layers"] = n
        return self.replace(**kw)


_REGISTRY: dict[str, ArchConfig] = {}


def register(cfg: ArchConfig) -> ArchConfig:
    if cfg.name in _REGISTRY:
        raise KeyError(f"duplicate arch id {cfg.name!r}")
    _REGISTRY[cfg.name] = cfg
    return cfg


def get_config(name: str) -> ArchConfig:
    # import side-effect registration
    from repro import configs as _c  # noqa: F401
    _c.load_all()
    if name not in _REGISTRY:
        raise KeyError(f"unknown arch {name!r}; have {sorted(_REGISTRY)}")
    return _REGISTRY[name]


def all_configs() -> dict[str, ArchConfig]:
    from repro import configs as _c
    _c.load_all()
    return dict(_REGISTRY)
