"""Compute backends: per-block dispatch between reference XLA and fused Pallas.

The PrecisionPlan decides *what* is quantized; the **compute backend**
decides *how* each quantized block executes. The registry holds three
backends (see ``docs/architecture.md`` for the full dispatch table):

* ``reference`` — the composable XLA ops the substrate always had
  (``repro.models.layers``: float ``dot_general`` / ``int8_matmul``). This
  backend *declines* every op, so model code falls through to its inline
  implementation — backend=None and backend="reference" are byte-identical.
* ``fused``     — the Pallas kernels in this package: block GEMMs through
  ``quant_linear`` (dequant + bias + activation fused into the epilogue),
  the attn→ffn residual boundary through ``addnorm_quant`` (emitting the
  int8 tensor the FFN input GEMM consumes — the paper's Figure-2 int8
  inter-kernel dataflow), per-token activation scales through
  ``dynamic_quant``, and the embedding gather through ``fused_embed``.
  Float blocks, MoE/MLA/recurrent bodies, and observer-capture runs keep
  the reference path — dispatch is per-op, driven by the parameter leaves
  the plan produced (QuantizedTensor weights + ``xs`` scales).
* ``auto``      — ``fused`` where the platform compiles it (TPU / Mosaic),
  ``reference`` everywhere else. On a CPU container the kernels only run in
  interpret mode (a correctness tool, not a fast path), so ``auto``
  resolves to reference there.

Backends are instantiated via :func:`get_backend` (a name or an instance);
every op either returns a result or ``None`` ("decline — use the reference
path"), which is what makes per-op fallback structural rather than
flag-driven. The backend's ``name`` is part of the serving runtime's
executable-cache key, next to the plan fingerprint and (for meshed
deployments) the mesh topology fingerprint.

Mosaic kernels cannot be partitioned by the compiler. On a data-parallel
mesh (model axis 1) the serving runtime runs each whole step per device
(``shard_map``), so the backend sees one device's rows and needs no mesh.
Where the compiler partitions the step (a model axis above 1), the
runtime binds the backend via :meth:`with_mesh`, and the fused backend
then declines every op: that serving runs the reference path.
"""
from __future__ import annotations

import copy
import dataclasses
import math
from typing import Any, Optional, Union

import jax
import jax.numpy as jnp

from repro.core.quantize import QuantizedTensor, quantize
from repro.kernels.quant_linear import ACTIVATIONS

#: activation functions a fused GEMM epilogue can apply — exactly the
#: kernel's own table, so a new activation is fusable the moment the
#: kernel (and the reference path, which shares the table) supports it.
FUSABLE_ACTS = tuple(ACTIVATIONS)


@dataclasses.dataclass
class QuantActivation:
    """A pre-quantized activation handed between fused ops inside one trace:
    the int8 layer-boundary tensor of the paper's Figure 2 (green arrows),
    plus the float dtype the consumer should emit. Produced by the fused
    ``addnorm`` op, consumed by the next block's ``linear``."""

    q: QuantizedTensor
    out_dtype: Any

    @property
    def shape(self):
        return self.q.values.shape

    @property
    def dtype(self):
        return self.out_dtype

    def dequantize(self) -> jax.Array:
        return self.q.dequantize(self.out_dtype)

    def reshape(self, *shape) -> "QuantActivation":
        """Reshape the int8 payload (scales are per-tensor scalars for every
        producer in this package), so model-code reshapes between GEMMs —
        e.g. the (B, S, H, hd) -> (B, S, q_dim) head fold before attn_out —
        work on pre-quantized activations unchanged."""
        if len(shape) == 1 and isinstance(shape[0], (tuple, list)):
            shape = tuple(shape[0])
        return QuantActivation(
            QuantizedTensor(self.q.values.reshape(shape), self.q.scale,
                            self.q.zero_point), self.out_dtype)

    def transpose(self, *axes) -> "QuantActivation":
        if len(axes) == 1 and isinstance(axes[0], (tuple, list)):
            axes = tuple(axes[0])
        return QuantActivation(
            QuantizedTensor(self.q.values.transpose(axes), self.q.scale,
                            self.q.zero_point), self.out_dtype)


def ffn_input_scale(ffn_p: dict, ffn_kind: str) -> Optional[jax.Array]:
    """The static activation scale the layer's ffn_in GEMMs were calibrated
    with — present iff the plan made the block int8 with static acts. This
    is the requant scale the fused addnorm kernel needs to emit the int8
    tensor those GEMMs consume."""
    key = "wg" if ffn_kind == "glu" else "wi"
    sub = ffn_p.get(key)
    if not isinstance(sub, dict) or not isinstance(sub.get("w"),
                                                   QuantizedTensor):
        return None
    return sub.get("xs")


class ComputeBackend:
    """Reference backend: decline every op so model code runs its inline
    XLA implementation. Also the base class fused backends extend."""

    name = "reference"

    def linear(self, x, p: dict, *, act: Optional[str] = None):
        """One block GEMM: x (..., K) @ p["w"] (+ bias) (+ activation).
        Return the result, or None to use the caller's reference path."""
        return None

    def addnorm(self, delta, residual, p: dict, kind: str, next_scale,
                eps: float = 1e-6):
        """The residual boundary: (residual + delta, norm(...)), requantized
        for the next GEMM when ``next_scale`` is its static act scale.
        Return (new_residual, norm_out_or_QuantActivation), or None."""
        return None

    def embed(self, tokens, p: dict, cfg, *, positions, segments,
              compute_dtype):
        """Token(+position)(+segment) embedding. Return (B, S, D), or None
        to use the reference gather."""
        return None

    def expert_gemm(self, xe, w, xs=None):
        """Routed MoE expert GEMM: xe (..., E, C, D) @ w.values (E, D, F)
        with per-expert scale operands (weight scales (E, 1, F); static
        activation scales (E, 1, 1) under the v4 ``experts`` family, or
        per-token dynamic when ``xs`` is None). Return (..., E, C, F), or
        None to use the reference batched einsum."""
        return None

    def attention(self, q, k, v, p: dict, *, k_pos, spec, scale,
                  softcap=None):
        """Whole fully-quantized encoder attention core (QK^T + softmax +
        P·V). ``q``/``k``/``v`` are (B, S, H, hd) float; ``p`` the attention
        param dict carrying the calibrated ``q/k/p/v_scale`` operands.
        Return (B, Sq, Hq, hd) — possibly a QuantActivation when the layer's
        ``norm='int8'`` span requantizes the output for the attn_out GEMM —
        or None to use the reference :func:`attention_core` path."""
        return None

    def decode_attention(self, q, kv_cache, pages, *, positions, active,
                         scale, softcap=None, static_scales=None,
                         p_scale=None):
        """Single-token decode attention over a paged KV cache. ``q`` is
        (B, 1, Hq, hd); ``kv_cache`` the paged cache dict (``pages_k``/...);
        ``pages`` the (B, pages_per_slot) table. Return (B, 1, Hq, hd), or
        None to use the reference gather-dequant + attention_core path."""
        return None

    def pages_in_place(self, kv_cache) -> bool:
        """Whether this backend writes a paged cache's pool in place
        (:meth:`write_pages`) on a decode step. Its pool leaves
        (``layers.POOL_KEYS``) then reach the layer whole, stacked over
        the scan group's layers, with the layer index under
        ``layers.POOL_LAYER``. The reference backend scatters in XLA:
        False."""
        return False

    def page_lanes(self) -> int:
        """The lane width the minor dim of int8 page leaves is padded to
        (``layers.page_leaf_shape``), so that their compact device layout
        is the row-major one this backend's kernels read: 1, unpadded,
        where no kernel reads them."""
        return 1

    def write_pages(self, kv_cache, rows: dict, page, row) -> dict:
        """Write one new row per slot into the pool leaves ``rows`` names
        (``pages_k`` -> (B, Hkv, hd) int8, ``pages_ks`` -> (B, Hkv) float
        scales, ...) at ``(page[b], row[b])``, ``page`` -1 writing nothing;
        returns the updated leaves. Called only where
        :meth:`pages_in_place` holds."""
        raise NotImplementedError

    # -- mesh binding --------------------------------------------------------
    def with_mesh(self, mesh) -> "ComputeBackend":
        """Bind this backend to a mesh the compiler partitions the step
        over. The reference backend is sharding-oblivious (XLA/GSPMD
        partitions its ops natively), so the base implementation returns
        self; the fused backend returns a copy that declines every op."""
        return self

    # -- plan validation -----------------------------------------------------
    def supports(self, spec) -> bool:
        """Whether this backend can execute a QuantSpec. The built-ins
        execute every constructible spec (reference ops are the universal
        per-op fallback); registered custom backends with a narrower op
        set override this."""
        return True

    def validate_plan(self, precision) -> None:
        """Fail at apply time — not serve time — if the plan names a spec
        :meth:`supports` rejects. A no-op for the built-in backends; the
        hook exists for custom registered backends."""
        from repro.core.plan import BLOCKS, BLOCK_FAMILIES
        bad = [(i, b) for i, lp in enumerate(precision.layers)
               for b in BLOCKS if not self.supports(lp.spec(b))]
        # schema-v4 block families: only families the layer actually sets
        # are validated (the fallback spec is already covered above)
        bad += [(i, f) for i, lp in enumerate(precision.layers)
                for f in BLOCK_FAMILIES
                if getattr(lp, f) is not None
                and not self.supports(getattr(lp, f))]
        if bad:
            shown = ", ".join(f"layer{i}/{b}" for i, b in bad[:4])
            raise ValueError(
                f"backend {self.name!r} cannot execute {len(bad)} "
                f"block(s): {shown}{', ...' if len(bad) > 4 else ''}")

    def describe(self) -> str:
        return self.name

    def __repr__(self) -> str:
        return f"<{type(self).__name__} {self.name!r}>"


class FusedBackend(ComputeBackend):
    """Pallas-fused backend: int8 blocks hit the fused kernels, float blocks
    and unsupported bodies keep the reference path (per-op fallback)."""

    name = "fused"

    def __init__(self, enabled: bool = True):
        # ``enabled=False`` turns every op into a decline — the AutoBackend
        # constructor uses it to resolve to reference off-TPU.
        self._enabled = enabled
        # why a mesh turned every op into a decline (None: not declined);
        # describe() then says so, since no kernel will be compiled
        self._declined_for: Optional[str] = None

    def with_mesh(self, mesh) -> "FusedBackend":
        b = copy.copy(self)
        if mesh is not None and self._enabled:
            # Mosaic kernels cannot be partitioned by the compiler
            b._enabled = False
            b._declined_for = ("tensor-parallel mesh"
                               if int(mesh.shape.get("model", 1)) > 1
                               else "partitioned mesh")
        return b

    def describe(self) -> str:
        return (f"fused[declined: {self._declined_for}]"
                if self._declined_for else self.name)

    # -- block GEMM ----------------------------------------------------------
    def linear(self, x, p: dict, *, act: Optional[str] = None):
        w = p.get("w")
        if (not self._enabled or not isinstance(w, QuantizedTensor)
                or w.values.ndim != 2 or act not in FUSABLE_ACTS):
            return None          # float block / expert stack: reference path
        K, N = w.values.shape
        from repro.kernels import ops
        if not (ops.lane_tiled(K) and ops.lane_tiled(N)):
            return None          # no whole-lane tiling: reference path
        if isinstance(x, QuantActivation):
            # already int8 — the fused addnorm quantized it with the static
            # scale this GEMM was calibrated on; no runtime quant needed
            out_dtype = x.out_dtype
            lead = x.q.values.shape[:-1]
            x_q = x.q.values.reshape(-1, K)
            x_scale = x.q.scale
        else:
            out_dtype = x.dtype
            lead = x.shape[:-1]
            x2 = x.reshape(-1, K)
            xs = p.get("xs")
            if xs is not None:                     # static per-tensor scale
                x_q, x_scale = quantize(x2, xs), xs
            else:                                  # per-token dynamic scales
                x_q, x_scale = ops.dynamic_quant(x2)
        w_scale = w.scale.astype(jnp.float32).reshape(-1)
        if w_scale.shape[0] != N:                  # int8_per_tensor weights
            w_scale = jnp.broadcast_to(w_scale, (N,))
        # ``out_xs`` — attached by apply_plan under a norm='int8' span — is
        # the next consumer's calibrated activation scale: the epilogue
        # requantizes to int8 and the result stays quantized between GEMMs.
        out_xs = p.get("out_xs")
        y = ops.quant_linear(x_q, w.values, w_scale, x_scale,
                             bias=p.get("b"), act=act, out_scale=out_xs,
                             out_dtype=out_dtype)
        y = y.reshape(lead + (N,))
        if out_xs is not None:
            return QuantActivation(
                QuantizedTensor(y, jnp.asarray(out_xs, jnp.float32), None),
                out_dtype)
        return y

    # -- routed expert GEMM stack --------------------------------------------
    def expert_gemm(self, xe, w, xs=None):
        # Claims int8 expert stacks: one grouped quant_expert_gemm kernel
        # per GEMM, its grid walking the experts, each with its own
        # per-expert scale operands (weights (E, 1, F); static acts
        # (E, 1, 1) — a scalar xs, the pre-v4 ffn_in fallback, is every
        # expert's). Declines float stacks.
        if (not self._enabled or not isinstance(w, QuantizedTensor)
                or w.values.ndim != 3):
            return None
        from repro.kernels import ops
        return ops.quant_expert_gemm(xe, w.values, w.scale, xs,
                                     out_dtype=jnp.float32)

    # -- residual boundary ---------------------------------------------------
    def addnorm(self, delta, residual, p: dict, kind: str, next_scale,
                eps: float = 1e-6):
        if not self._enabled or next_scale is None or residual.ndim != 3:
            return None
        from repro.kernels import ops
        B, S, D = residual.shape
        if isinstance(delta, QuantActivation):
            # the producing GEMM requantized its output (norm='int8' span):
            # hand the int8 payload straight through; the kernel dequantizes
            # it in-register via the x_in_scale operand.
            d2, d_scale = delta.q.values.reshape(-1, D), delta.q.scale
        else:
            d2, d_scale = delta.reshape(-1, D), None
        h2, q2 = ops.addnorm_quant(
            d2, residual.reshape(-1, D),
            jnp.zeros((D,), jnp.float32),          # biases already applied
            p["scale"], p.get("bias"), next_scale, x_in_scale=d_scale,
            kind=kind, eps=eps)
        qa = QuantActivation(
            QuantizedTensor(q2.reshape(B, S, D),
                            jnp.asarray(next_scale, jnp.float32), None),
            residual.dtype)
        return h2.reshape(B, S, D), qa

    # -- embedding -----------------------------------------------------------
    def embed(self, tokens, p: dict, cfg, *, positions, segments,
              compute_dtype):
        # learned-position archs only (the paper's BERT family); rope archs
        # have no position table to gather and keep the reference path
        if not self._enabled or "pos" not in p or cfg.frontend is not None:
            return None
        from repro.kernels import ops
        B, S = tokens.shape
        pos = jnp.broadcast_to(jnp.asarray(positions, jnp.int32), (B, S))
        seg_table = seg = None
        if "seg" in p and segments is not None:
            seg_table = p["seg"]
            seg = jnp.asarray(segments).reshape(-1)
        x = ops.fused_embed(tokens.reshape(-1), p["tok"], p["pos"],
                            seg_table, seg, positions=pos.reshape(-1),
                            out_dtype=compute_dtype)
        x = x.reshape(B, S, -1)
        # scale/emb-norm epilogue mirrors repro.models.layers.embed exactly
        # (function-local import: layers imports this module at top level)
        if cfg.emb_scale_by_sqrt_dim:
            x = x * jnp.asarray(math.sqrt(cfg.d_model), compute_dtype)
        if "emb_norm" in p:
            from repro.models.layers import layer_norm
            x = layer_norm(x, p["emb_norm"])
        return x


    # -- fully-quantized encoder attention -----------------------------------
    def attention(self, q, k, v, p: dict, *, k_pos, spec, scale,
                  softcap=None):
        # Claims the bidirectional (encoder) core when the plan calibrated
        # all four scheme scales — the softmax='uint8' dataflow. Causal /
        # windowed masks keep the reference path (the kernel holds the
        # whole key axis per tile and masks on validity only). GQA is
        # supported: the kernel's head grid indexes kv heads by division.
        if (not self._enabled or spec.causal or spec.window is not None
                or any(f"{s}_scale" not in p for s in ("q", "k", "p", "v"))):
            return None
        B, Sq, Hq, hd = q.shape
        if Hq % k.shape[2] != 0:
            return None
        qh = q.transpose(0, 2, 1, 3)               # (B, H, S, hd)
        kh = k.transpose(0, 2, 1, 3)
        vh = v.transpose(0, 2, 1, 3)
        # quantize operands host-side at the calibrated scales; the score
        # scaling rides the q quantization (same as the reference quant_bmm
        # which quantizes q * rsqrt(d))
        qq = quantize(qh * jnp.asarray(scale, qh.dtype), p["q_scale"])
        kq = quantize(kh, p["k_scale"])
        vq = quantize(vh, p["v_scale"])
        # requantize the attention output at the attn_out GEMM's calibrated
        # activation scale (wo["xs"]) so the span's first hop is int8
        wo = p.get("wo", {})
        o_scale = wo.get("xs") if isinstance(wo.get("w"), QuantizedTensor) \
            else None
        from repro.kernels import ops
        k_pos = jnp.broadcast_to(jnp.asarray(k_pos, jnp.int32).reshape(
            -1, kq.shape[2]), (B, kq.shape[2]))
        out = ops.quant_flash_attention(
            qq, kq, vq, k_pos, q_scale=p["q_scale"], k_scale=p["k_scale"],
            p_scale=p["p_scale"], v_scale=p["v_scale"], o_scale=o_scale,
            softcap=softcap, out_dtype=q.dtype)
        out = out.transpose(0, 2, 1, 3)            # (B, Sq, Hq, hd)
        if o_scale is not None:
            return QuantActivation(
                QuantizedTensor(out, jnp.asarray(o_scale, jnp.float32),
                                None), q.dtype)
        return out

    # -- paged decode attention ----------------------------------------------
    def decode_attention(self, q, kv_cache, pages, *, positions, active,
                         scale, softcap=None, static_scales=None,
                         p_scale=None):
        # The kernel's win is skipping the float-cache materialization, so
        # it claims int8 pages only; float paged caches (and MLA's latent
        # pages) keep the XLA gather path.
        k, v = kv_cache.get("pages_k"), kv_cache.get("pages_v")
        if not self._enabled or k is None or v is None \
                or k.dtype != jnp.int8:
            return None
        per_token = "pages_ks" in kv_cache
        if per_token:
            ks, vs = kv_cache["pages_ks"], kv_cache["pages_vs"]
        else:
            sc = static_scales or {}
            if "k" not in sc or "v" not in sc:
                return None
            ks = sc["k"].astype(jnp.float32).reshape(-1)
            vs = sc["v"].astype(jnp.float32).reshape(-1)
        from repro.kernels import ops
        from repro.models.layers import POOL_LAYER
        B, S, Hq, hd = q.shape
        Hkv = k.shape[-3]                    # (..., Hkv, ps, hd)
        if S != 1 or Hq % Hkv != 0:
            return None
        pos = jnp.asarray(positions, jnp.int32)
        pos = jnp.broadcast_to(pos.reshape(-1)[0], (B,)) \
            if pos.ndim == 1 else pos[:, 0]
        lengths = pos + 1                    # incl. the token written above
        if active is not None:
            lengths = jnp.where(active, lengths, 0)
        out = ops.decode_attention(
            q[:, 0].reshape(B, Hkv, Hq // Hkv, hd), k, v, pages, lengths,
            k_scale=ks, v_scale=vs, per_head=not per_token,
            scale=float(scale),
            softcap=float(softcap) if softcap is not None else None,
            p_scale=p_scale, layer=kv_cache.get(POOL_LAYER))
        return out.reshape(B, 1, Hq, hd)

    # -- in-place page writes ------------------------------------------------
    def pages_in_place(self, kv_cache) -> bool:
        k = kv_cache.get("pages_k") if isinstance(kv_cache, dict) else None
        return self._enabled and k is not None and k.dtype == jnp.int8

    def page_lanes(self) -> int:
        from repro.kernels import ops
        return ops.page_lanes() if self._enabled else 1

    def write_pages(self, kv_cache, rows: dict, page, row) -> dict:
        from repro.kernels import ops
        from repro.models.layers import POOL_LAYER
        names = sorted(rows)
        layer = kv_cache.get(POOL_LAYER)
        pools = tuple(kv_cache[n] for n in names)
        if layer is None:                    # one layer's pool: a stack of 1
            pools = tuple(p[None] for p in pools)
        out = ops.page_write(pools, tuple(rows[n] for n in names),
                             0 if layer is None else layer, page, row)
        if layer is None:
            out = tuple(o[0] for o in out)
        return dict(zip(names, out))


class AutoBackend(FusedBackend):
    """Fused where the platform supports compiled Pallas (TPU), reference
    elsewhere — interpret mode is a correctness tool, not a serving path."""

    name = "auto"

    def __init__(self):
        super().__init__(enabled=jax.default_backend() == "tpu")

    def describe(self) -> str:
        return f"auto[{'fused' if self._enabled else 'reference'}]"


BACKENDS: dict[str, type] = {
    "reference": ComputeBackend,
    "fused": FusedBackend,
    "auto": AutoBackend,
}


def register_backend(name: str, cls: type) -> type:
    BACKENDS[name] = cls
    return cls


def get_backend(backend: Union[str, ComputeBackend, None]) -> ComputeBackend:
    """Resolve a backend name (or pass an instance through). ``None`` means
    reference — the substrate's inline ops."""
    if backend is None:
        return ComputeBackend()
    if isinstance(backend, ComputeBackend):
        return backend
    try:
        cls = BACKENDS[backend]
    except (KeyError, TypeError):
        raise KeyError(f"unknown compute backend {backend!r}; have "
                       f"{sorted(BACKENDS)}") from None
    return cls()
