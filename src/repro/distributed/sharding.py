"""Logical-axis sharding rules (t5x/MaxText-style) for every arch family.

The production mesh is ``(data=16, model=16)`` per pod, with a leading pure-DP
``pod`` axis for multi-pod (DESIGN.md §5). Rules here map parameter/
activation/cache tensors onto that mesh:

* **TP** over ``model``: attention q/k/v out-features, FFN hidden, vocab.
  A dim gets 'model' only when divisible by the axis size — non-divisible
  head counts fall back to replication for params (no padded param memory),
  while *activations* may use padded sharding (GSPMD pads transparently;
  the waste shows up honestly in the roofline FLOPs).
* **FSDP (ZeRO-3)** over ``data``: the d_model axis of every large matrix is
  sharded over the data axis; XLA all-gathers per layer inside the scan and
  reduce-scatters gradients. Optimizer state inherits param specs, so
  params+grads+moments are all fully sharded.
* **EP** over ``data``: expert-stacked weights shard E over data when
  divisible (deepseek-v2's 160), else FSDP over d_model (mixtral's 8) —
  per-expert TP over ``model`` either way.
* the ``pod`` axis never appears in param specs (pure DP: replicated params,
  gradient all-reduce over DCN — optionally int8-compressed, see
  repro.distributed.compression).

Everything is *rules by leaf path + shape divisibility*, so the same code
shards all 11 archs, both precisions, and any mesh shape. Quantized param
trees (the output of :func:`repro.quant.ptq.apply_plan`) need no extra
rules:

* int8 ``values`` leaves inherit the column/row TP spec of the float
  weight they replaced (the path is the weight's path + ``/values``);
* per-channel ``scale`` leaves shard along the same output axis as their
  weight — the broadcast (size-1) dims are forced unsharded, so a
  ``(1, N)`` scale rides the weight's ``N``-axis spec;
* per-tensor scales, ``zero_point`` scalars, static activation scales
  (``xs``) and the attention bmm scalars (``q/k/p/v_scale``) replicate
  (their non-stack shape is all-1 or 0-rank).

Serving consumes the same rules (``fsdp=False`` — inference replicates
params over the data axis and shards batches over it; see
serve/runtime.py); :func:`mesh_fingerprint` is the topology component of
the serving runtime's executable-cache key.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.models.layers import PAGE_HEAD_AXIS


@dataclasses.dataclass(frozen=True)
class MeshAxes:
    data: str = "data"
    model: str = "model"
    pod: Optional[str] = None      # present on the multi-pod mesh

    @property
    def dp(self) -> tuple:
        """Axes that shard the batch (pod is pure-DP)."""
        return (self.pod, self.data) if self.pod else (self.data,)


def infer_axes(mesh: Mesh) -> MeshAxes:
    names = mesh.axis_names
    return MeshAxes(pod="pod" if "pod" in names else None)


def mesh_fingerprint(mesh: Optional[Mesh]) -> str:
    """Stable topology identity for cache keys: axis names + sizes in mesh
    order (``"data=2,model=1"``), ``"unmeshed"`` for ``None``. Two meshes
    with the same fingerprint compile identical executables; anything that
    caches mesh-placed executables must fold this in (the serving runtime
    keys on it next to the backend name and plan fingerprint)."""
    if mesh is None:
        return "unmeshed"
    return ",".join(f"{a}={int(mesh.shape[a])}" for a in mesh.axis_names)


# --- param rules: (regex on "/"-joined path, spec builder) -------------------
# Spec builders receive (shape_without_stack_dim, sizes) and return a spec
# tuple for those dims. `F` = fsdp axis ('data'), `M` = tp axis ('model').

def _div(dim: int, size: int) -> bool:
    return dim % size == 0


class Rules:
    """Parameter sharding rule engine bound to (cfg, mesh)."""

    def __init__(self, cfg: ArchConfig, mesh: Mesh, *, fsdp: bool = True):
        self.cfg = cfg
        self.mesh = mesh
        self.axes = infer_axes(mesh)
        self.msize = mesh.shape["model"]
        self.dsize = mesh.shape["data"]
        self.fsdp = fsdp

    # -- helpers -------------------------------------------------------------
    def _f(self, dim: int):
        return self.axes.data if self.fsdp and _div(dim, self.dsize) else None

    def _m(self, dim: int):
        return self.axes.model if _div(dim, self.msize) else None

    def _col(self, shape):       # (D_in, N_out): FSDP in, TP out
        return (self._f(shape[0]), self._m(shape[1]))

    def _row(self, shape):       # (N_in, D_out): TP in, FSDP out
        return (self._m(shape[0]), self._f(shape[1]))

    def _expert(self, shape, row: bool):
        E = shape[0]
        if _div(E, self.dsize):
            # EP over data + per-expert TP over model
            return ((self.axes.data, self._m(shape[1]), None) if row
                    else (self.axes.data, None, self._m(shape[2])))
        # FSDP the d_model dim instead (few-expert archs)
        return ((None, self._m(shape[1]), self._f(shape[2])) if row
                else (None, self._f(shape[1]), self._m(shape[2])))

    # -- the rule table --------------------------------------------------------
    _COL = ("wq/w", "wk/w", "wv/w", "wg/w", "wu/w", "wi/w", "wz/w", "wx/w",
            "up/w", "wq_b/w", "wq_a/w", "wkv_b/w", "wa/w")
    _ROW = ("wo/w", "wd/w", "down/w", "proj/w")

    def spec_body(self, path: str, shape) -> tuple:
        """Spec for the trailing (non-stack) dims of a layer-body leaf."""
        c = self.cfg
        if re.search(r"ffn/(wg|wu|wd)/w$", path) and len(shape) == 3:
            return self._expert(shape, row=path.endswith("wd/w"))
        if re.search(r"ffn/(wg|wu|wd)/xs$", path) and len(shape) == 3:
            # per-expert static activation scales (E, 1, 1): ride the same
            # expert axis as the int8 values they dequantize (EP over data
            # when divisible); replicate for the FSDP fallback, whose
            # sharded d_model axis they do not carry
            return ((self.axes.data if _div(shape[0], self.dsize) else None),
                    None, None)
        if path.endswith("router/w"):
            return (None, None)
        if re.search(r"rec/(wa|wi)/w$", path):      # (R, R) gate GEMMs
            return (None, self._m(shape[1]))
        if re.search(r"blk/(wq|wk|wv|wif)/w$", path):
            return (None, self._m(shape[1]))
        if re.search(r"blk/(wi|wf|wo|wz)/w$", path):
            return (None, self._m(shape[1]))
        if any(path.endswith(s) for s in self._ROW):
            return self._row(shape)
        if any(path.endswith(s) for s in self._COL):
            return self._col(shape)
        if path.endswith("/b"):                     # biases follow out dim
            return (self._m(shape[-1]),)
        if path.endswith("wkv_a/w"):
            return (self._f(shape[0]), None)
        # norms / lam / conv / recurrent r / scales: replicate
        return (None,) * len(shape)

    def spec_for(self, path: str, shape) -> P:
        """Full spec for any param leaf (handles the group stack dim and
        QuantizedTensor scale/zero_point leaves)."""
        for suf in ("/values", "/scale", "/zero_point"):
            if path.endswith(suf):
                path = path[: -len(suf)]
                break
        in_body = "/layers/" in path
        if in_body:
            stack, body_shape = shape[:1], tuple(shape[1:])
        else:
            stack, body_shape = (), tuple(shape)
        if not body_shape:                          # scalars (zero_point)
            return P()
        if in_body:
            base = self.spec_body(path, body_shape)
        else:
            base = self._top_level(path, body_shape)
        # scale leaves: same rank as w but with broadcast dims of size 1
        base = tuple(None if body_shape[i] == 1 else base[i]
                     for i in range(len(base)))
        return P(*((None,) * len(stack) + base))

    def _top_level(self, path: str, shape) -> tuple:
        if path.endswith("embed/tok"):
            # Tied tables double as the LM head: shard the vocab over
            # 'model' so logits come out vocab-parallel (Megatron column-
            # parallel head) — the gather pays an all-gather of the table,
            # the (tokens x vocab) logits never replicate. Untied tables
            # are gather-only: shard d_model instead (local gather).
            if self.cfg.tie_embeddings:
                return (self._m(shape[0]), None)
            return (None, self._m(shape[1]))
        if path.endswith("embed/pos") or path.endswith("embed/seg"):
            return (None, self._m(shape[1]))
        if "lm_head" in path and path.endswith("/w"):
            return (self._f(shape[0]), self._m(shape[1]))
        if "frontend_proj" in path and path.endswith("/w"):
            return (None, self._m(shape[1]))
        if len(shape) == 2:
            return (None, None)
        return (None,) * len(shape)

    # -- public API -------------------------------------------------------------
    def params_spec(self, params) -> dict:
        """PartitionSpec pytree matching ``params`` (works on arrays or
        ShapeDtypeStructs)."""
        flat, treedef = jax.tree_util.tree_flatten_with_path(params)
        specs = []
        for kp, leaf in flat:
            path = _path_str(kp)
            specs.append(self.spec_for(path, leaf.shape))
        return jax.tree_util.tree_unflatten(treedef, specs)

    def params_sharding(self, params):
        return jax.tree_util.tree_map(
            lambda s: NamedSharding(self.mesh, s), self.params_spec(params),
            is_leaf=lambda x: isinstance(x, P))

    @property
    def dp_size(self) -> int:
        """Total batch-sharding factor (product of the dp axes). Serving
        rounds batch buckets up to multiples of this so request batches
        always split evenly over the data axis."""
        bsz = 1
        for a in self.axes.dp:
            bsz *= self.mesh.shape[a]
        return bsz

    def batch_spec(self, batch) -> dict:
        dp = self.axes.dp
        bsz = self.dp_size

        def spec(leaf):
            if leaf.ndim == 0:
                return P()
            b = P(dp) if leaf.shape[0] % bsz == 0 else P()
            return P(*(b + (None,) * (leaf.ndim - 1)))
        return jax.tree_util.tree_map(spec, batch)

    def batch_sharding(self, batch):
        return jax.tree_util.tree_map(
            lambda s: NamedSharding(self.mesh, s), self.batch_spec(batch),
            is_leaf=lambda x: isinstance(x, P))

    def cache_spec(self, caches) -> list:
        """Decode caches: batch over dp where divisible; kv-heads over model
        when divisible, else the sequence (slot) axis takes model."""
        dp = self.axes.dp
        bsz = 1
        for a in dp:
            bsz *= self.mesh.shape[a]

        def leaf_spec(kp, leaf):
            path = _path_str(kp)
            shape = leaf.shape        # (steps, B, ...) or (steps, W)
            if leaf.ndim <= 2 or path.endswith("k_pos") or \
                    path.endswith("pos"):
                return P(*(None,) * leaf.ndim)
            if "pages_" in path:
                # paged pool leaves (steps, NP, ...): page ids are global —
                # the pool axis never shards (a slot's table may reference
                # any page), and the batch axis isn't there at all. Only
                # the kv-head axis of the head-major K/V pages and their
                # scales (after the leading steps axis) may take 'model'.
                spec = [None] * leaf.ndim
                head = 1 + PAGE_HEAD_AXIS
                if (path.endswith("pages_k") or path.endswith("pages_v")
                        or path.endswith("pages_ks")
                        or path.endswith("pages_vs")) \
                        and _div(shape[head], self.msize):
                    spec[head] = self.axes.model
                return P(*spec)
            b = dp if shape[1] % bsz == 0 else None
            if path.endswith("/k") or path.endswith("/v"):
                # (steps, B, W, Hkv, hd)
                if _div(shape[3], self.msize):
                    return P(None, b, None, self.axes.model, None)
                return P(None, b, self.axes.model, None, None)
            if path.endswith("ckv") or path.endswith("krope"):
                return P(None, b, self.axes.model, None)
            if path.endswith("/C"):   # mlstm matrix state (steps,B,H,dk,dv)
                return P(None, b, None, None, None)
            return P(*((None, b) + (None,) * (leaf.ndim - 2)))

        flat, treedef = jax.tree_util.tree_flatten_with_path(caches)
        return jax.tree_util.tree_unflatten(
            treedef, [leaf_spec(kp, l) for kp, l in flat])

    def seq_shard_attn(self, B: int, S: int, H: int,
                       budget_bytes: float = 6e9) -> bool:
        """Context-parallel attention is on when the sequence splits evenly
        over 'model' AND the resulting unchunked per-device score tensor
        fits a VMEM-friendly HBM budget (no query-chunk scan needed —
        chunked scans cannot slice a sharded axis without serializing)."""
        if S % self.msize or S < self.msize:
            return False
        bsz = self.dsize * (self.mesh.shape.get("pod", 1)
                            if self.axes.pod else 1)
        b_loc = max(B // max(bsz, 1), 1)
        score_bytes = b_loc * H * (S // self.msize) * S * 4.0
        return score_bytes <= budget_bytes

    def attn_chunk(self, B: int, S: int, H: int, default: int = 512):
        """Query-chunk size matching the sharding choice (None = unchunked,
        used when attention is sequence-sharded)."""
        return None if self.seq_shard_attn(B, S, H) else default

    def constrain(self, x: jax.Array, tag: str) -> jax.Array:
        """Activation sharding constraints threaded through model code."""
        dp = self.axes.dp
        m = self.axes.model
        bsz = 1
        for a in dp:
            bsz *= self.mesh.shape[a]
        b_ax = dp if x.shape[0] % bsz == 0 else None
        if tag in ("activation", "residual"):
            spec = P(b_ax, None, None)
        elif tag == "logits":
            spec = P(b_ax, None, m if _div(x.shape[-1], self.msize) else None)
        elif tag == "moe_tokens":        # (G, Tl, D): G = data shard groups
            g_ax = self.axes.data if _div(x.shape[0], self.dsize) else None
            spec = P(g_ax, None, None)
        elif tag == "moe_dispatch":      # (G, E, C, D) or (E, C, D)
            if x.ndim == 4:
                g_ax = (self.axes.data if _div(x.shape[0], self.dsize)
                        else None)
                spec = P(g_ax, None, None, None)
            elif _div(x.shape[0], self.dsize):
                spec = P(self.axes.data, None, None)
            else:
                spec = P(None, self.axes.data, None)
        elif tag == "moe_hidden":        # (G, E, C, F) or (E, C, F)
            f_ax = m if _div(x.shape[-1], self.msize) else None
            if x.ndim == 4:
                g_ax = (self.axes.data if _div(x.shape[0], self.dsize)
                        else None)
                spec = P(g_ax, None, None, f_ax)
            elif _div(x.shape[0], self.dsize):
                spec = P(self.axes.data, None, f_ax)
            else:
                spec = P(None, self.axes.data, f_ax)
        elif tag == "attn_scores":       # (B, H, Sq, Sk)
            B, H, Sq, Sk = x.shape
            if self.seq_shard_attn(B, Sk, H) and _div(Sq, self.msize):
                spec = P(b_ax, None, m, None)        # q-seq sharded
            elif _div(H, self.msize):
                spec = P(b_ax, m, None, None)        # head TP
            else:
                return x
        elif tag == "attn_heads":        # (B, S, H, hd)
            B, S, H, _ = x.shape
            if self.seq_shard_attn(B, S, H):
                # context parallelism: queries/keys seq-sharded over model;
                # scores + softmax stay seq-sharded (16x less HBM), K/V
                # all-gather is cheap relative
                spec = P(b_ax, m, None, None)
            elif _div(H, self.msize):
                spec = P(b_ax, None, m, None)     # clean head TP
            else:
                # neither seq nor heads shard cleanly: leave it to GSPMD —
                # forcing padded head sharding measured 7x worse (resharding
                # copies), and forcing replication wastes 16x attention
                # compute at 32k prefill
                return x
        else:
            return x
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, spec))

    # Rules doubles as the `constrain` callable threaded through model code;
    # model modules read rule metadata (e.g. `dsize` for the MoE token-group
    # dispatch) off it via getattr.
    def __call__(self, x: jax.Array, tag: str) -> jax.Array:
        return self.constrain(x, tag)


def _path_str(kp) -> str:
    parts = []
    for k in kp:
        if hasattr(k, "key"):               # DictKey
            parts.append(str(k.key))
        elif hasattr(k, "idx"):             # SequenceKey
            parts.append(str(k.idx))
        elif hasattr(k, "name"):            # GetAttrKey (QuantizedTensor)
            parts.append(str(k.name))
        else:
            parts.append(str(k))
    return "/".join(parts)
