"""The modular inference pipeline: tokenizer -> embedding -> encoder -> target.

The paper's §3.1 decomposition as first-class objects. Each stage is a thin,
independently-usable wrapper over the substrate (``repro.data.tokenizer``,
``repro.models.transformer``); :class:`Pipeline` composes them into exactly
the fused forward the substrate executes, so a Pipeline prediction is
bit-identical to the hand-rolled ``T.forward`` + ``T.apply_head`` closure it
replaces.

A Pipeline is built from an :class:`~repro.configs.base.ArchConfig` plus a
task spec (name or :class:`~repro.data.pipeline.TaskSpec`); the target head
is resolved from the ``TARGETS`` registry (default: the head matching the
task kind). ``predict()`` / ``eval()`` replace the hand-rolled eval_fn
closures of the old quickstart; ``with_policy()`` rebinds the same stages to
quantized params under a new execution plan (the post-PTQ pipeline).
"""
from __future__ import annotations

from typing import Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.core.plan import PrecisionPlan
from repro.data.pipeline import TaskSpec, eval_accuracy, get_batch, make_task
from repro.data.tokenizer import WordPieceTokenizer
from repro.kernels.backend import get_backend
from repro.models import layers as L
from repro.models import transformer as T
from repro.serve.runtime import Runtime
from repro.toolkit.registry import get_target
from repro.toolkit.targets import TARGET_FOR_TASK_KIND, TargetSpec


# ---------------------------------------------------------------------------
# stages
# ---------------------------------------------------------------------------


class TokenizerStage:
    """Raw text -> model inputs. Synthetic tasks arrive pre-tokenized, so
    the tokenizer is optional; when present (a
    :class:`~repro.data.tokenizer.WordPieceTokenizer`) ``encode_batch``
    produces padded ``tokens``/``segments`` ready for the embedding stage."""

    def __init__(self, tokenizer: Optional[WordPieceTokenizer] = None,
                 seq_len: int = 64):
        self.tokenizer = tokenizer
        self.seq_len = seq_len

    def __call__(self, texts: Sequence) -> dict:
        if self.tokenizer is None:
            raise ValueError("pipeline built without a tokenizer; feed "
                             "pre-tokenized batches or pass tokenizer=")
        if texts and isinstance(texts[0], (tuple, list)):   # sentence pairs
            ids = np.full((len(texts), self.seq_len),
                          self.tokenizer.index["[PAD]"], np.int32)
            seg = np.zeros((len(texts), self.seq_len), np.int32)
            for i, (a, b) in enumerate(texts):
                ti, si = self.tokenizer.encode_pair(a, b)
                ti, si = ti[:self.seq_len], si[:self.seq_len]
                ids[i, :len(ti)] = ti
                seg[i, :len(si)] = si
            return {"tokens": ids, "segments": seg}
        ids, _ = self.tokenizer.encode_batch(list(texts), self.seq_len)
        return {"tokens": ids,
                "segments": np.zeros_like(ids)}


class EmbeddingStage:
    """Model inputs -> first-layer activations (token + position + segment
    embeddings, or the modality frontend for audio/vision configs)."""

    def __init__(self, cfg: ArchConfig, backend=None):
        self.cfg = cfg
        self.backend = backend

    def __call__(self, params: dict, batch: dict, *, positions,
                 compute_dtype) -> jax.Array:
        return T.embed_inputs(params, batch, self.cfg, positions=positions,
                              compute_dtype=compute_dtype,
                              backend=self.backend)


class EncoderStage:
    """Activations -> final-norm hidden states under an execution plan (the
    per-layer SAMP precision modes compiled into scan groups), executed on
    a compute backend (reference XLA or fused Pallas kernels)."""

    def __init__(self, cfg: ArchConfig, plan, scheme: T.QuantScheme,
                 backend=None):
        self.cfg = cfg
        self.plan = plan
        self.scheme = scheme
        self.backend = backend

    def __call__(self, params: dict, x: jax.Array, *, positions) -> jax.Array:
        x, _, _ = T.run_groups(x, params, self.cfg, self.plan, self.scheme,
                               positions=positions, backend=self.backend)
        return L.norm(x, params["final_norm"], self.cfg.norm_kind)


class TargetStage:
    """Hidden states -> task logits via the registered head."""

    def __init__(self, spec: TargetSpec, n_out: int, cfg: ArchConfig):
        self.spec = spec
        self.n_out = n_out
        self.cfg = cfg

    def __call__(self, params: dict, hidden: jax.Array) -> jax.Array:
        return self.spec.apply(params, hidden, self.cfg)


# ---------------------------------------------------------------------------
# the pipeline
# ---------------------------------------------------------------------------


class Pipeline:
    """tokenizer -> embedding -> encoder -> target, under one
    :class:`~repro.core.plan.PrecisionPlan`. Hold one Pipeline per deployed
    configuration: ``with_policy`` derives the quantized sibling from PTQ
    output (and shares this pipeline's runtime — one executable cache,
    keyed by plan fingerprint)."""

    def __init__(self, cfg: ArchConfig, task: TaskSpec, target: TargetSpec,
                 *, n_out: Optional[int] = None,
                 policy: Optional[PrecisionPlan] = None,
                 plan=None, scheme: T.QuantScheme = T.QuantScheme(),
                 params: Optional[dict] = None,
                 tokenizer: Optional[WordPieceTokenizer] = None,
                 compute_dtype=jnp.float32, backend="reference",
                 mesh=None):
        self.cfg = cfg
        self.task = task
        self.backend = get_backend(backend)
        # serving mesh the runtime places executables over (None = single
        # device); quantized siblings and serving engines inherit it
        self.mesh = mesh
        self.policy = policy or PrecisionPlan.full_float(cfg.num_layers)
        self.scheme = scheme
        self.compute_dtype = compute_dtype
        self.params = params
        n_out = n_out if n_out is not None else max(task.n_classes, 1)
        # -- the four stages -------------------------------------------------
        self.tokenizer = TokenizerStage(tokenizer, task.seq_len)
        self.embedding = EmbeddingStage(cfg, backend=self.backend)
        self.encoder = EncoderStage(cfg, plan if plan is not None
                                    else T.build_plan(cfg, self.policy),
                                    scheme, backend=self.backend)
        self.target = TargetStage(target, n_out, cfg)
        self._runtime: Optional[Runtime] = None

    @classmethod
    def build(cls, cfg: ArchConfig, task: Union[str, TaskSpec], *,
              target: Optional[str] = None, n_out: Optional[int] = None,
              seq_len: int = 64, float_dtype: str = "bfloat16",
              scheme: T.QuantScheme = T.QuantScheme(),
              tokenizer: Optional[WordPieceTokenizer] = None,
              compute_dtype=None, backend="reference",
              mesh=None) -> "Pipeline":
        """ArchConfig + task spec -> float Pipeline (params uninitialized;
        call ``init_params`` or let the SAMP facade fine-tune).
        ``backend`` picks the compute backend quantized blocks execute on
        (reference | fused | auto — see repro.kernels.backend); ``mesh``
        (a jax Mesh with data/model axes) makes the runtime shard params
        and batches over it (see docs/serving.md)."""
        if isinstance(task, str):
            task = make_task(task, vocab_size=cfg.vocab_size,
                             seq_len=seq_len)
        spec = get_target(target or TARGET_FOR_TASK_KIND[task.kind])
        policy = PrecisionPlan.full_float(cfg.num_layers, float_dtype)
        if compute_dtype is None:
            compute_dtype = jnp.dtype(float_dtype) \
                if float_dtype != "float16" else jnp.float32
        return cls(cfg, task, spec, n_out=n_out, policy=policy,
                   scheme=scheme, tokenizer=tokenizer,
                   compute_dtype=compute_dtype, backend=backend, mesh=mesh)

    # -- construction --------------------------------------------------------
    @property
    def plan(self):
        return self.encoder.plan

    @property
    def precision(self) -> PrecisionPlan:
        """The pipeline's PrecisionPlan (alias of ``policy``)."""
        return self.policy

    @property
    def runtime(self) -> Runtime:
        """The bucketed-executable runtime this pipeline predicts through
        (and hands to the serving engines, so predict/serve/benchmark share
        one compilation cache). Params are call arguments — fine-tuning
        does not invalidate it. Cache keys fold the precision plan's
        fingerprint, so ``with_policy`` siblings share this runtime."""
        if self._runtime is None:
            spec, cfg = self.target.spec, self.cfg
            self._runtime = Runtime(
                cfg, self.plan, scheme=self.scheme,
                precision=self.precision,
                compute_dtype=self.compute_dtype,
                head=lambda p, h: spec.apply(p, h, cfg),
                token_level=spec.token_level, backend=self.backend,
                mesh=self.mesh)
        return self._runtime

    def init_params(self, key, dtype=jnp.float32) -> dict:
        """Float init: base model params + the target head's params."""
        kbase, khead = jax.random.split(key)
        params = T.init_params(kbase, self.cfg, self.policy, dtype=dtype)
        head = self.target.spec.init(khead, self.cfg, self.target.n_out,
                                     dtype)
        if head is not None:
            params["head"] = head
        self.params = params
        return params

    def with_policy(self, params: dict, plan,
                    policy: PrecisionPlan) -> "Pipeline":
        """Same stages, new precision: bind PTQ output (params packed under
        ``plan``) into a sibling Pipeline. The sibling shares this
        pipeline's runtime — its executables land in the same cache under
        the new plan's fingerprint, so float and quantized deployments of
        one model compile at most once per (plan, bucket)."""
        pipe = Pipeline(self.cfg, self.task, self.target.spec,
                        n_out=self.target.n_out, policy=policy, plan=plan,
                        scheme=self.scheme, params=params,
                        tokenizer=self.tokenizer.tokenizer,
                        compute_dtype=self.compute_dtype,
                        backend=self.backend, mesh=self.mesh)
        pipe._runtime = self.runtime.share(plan, scheme=self.scheme,
                                           precision=pipe.precision,
                                           backend=pipe.backend)
        return pipe

    # -- forward / predict ---------------------------------------------------
    def forward(self, params: dict, batch: dict) -> jax.Array:
        """Compose the stages: batch -> logits. Numerically identical to the
        substrate's fused ``T.forward`` (same functions, same order)."""
        lead = batch.get("tokens", batch.get("frames"))
        S = lead.shape[1]
        if self.cfg.frontend == "vision" and "prefix_embeds" in batch:
            S += batch["prefix_embeds"].shape[1]
        positions = jnp.arange(S, dtype=jnp.int32)
        x = self.embedding(params, batch, positions=positions,
                           compute_dtype=self.compute_dtype)
        hidden = self.encoder(params, x, positions=positions)
        return self.target(params, hidden)

    def _model_inputs(self, batch: dict) -> dict:
        keep = ("tokens", "segments", "frames", "prefix_embeds")
        return {k: jnp.asarray(v) for k, v in batch.items() if k in keep}

    def predict_logits(self, batch: dict) -> np.ndarray:
        """Task logits for one batch, via the runtime's bucketed executable
        cache (pads to the (batch, length) bucket; no retrace per shape)."""
        if self.params is None:
            raise ValueError("pipeline has no params; call init_params() "
                             "or load an artifact")
        return self.runtime.encode(self.params, self._model_inputs(batch))

    def predict(self, batch: dict) -> np.ndarray:
        """Predicted class ids for one batch (class per sequence, or per
        token for token-level targets)."""
        return np.asarray(self.target.spec.predict(
            self.predict_logits(batch)))

    def predict_texts(self, texts: Sequence) -> np.ndarray:
        """Raw strings (or (a, b) pairs for matching) -> predictions."""
        return self.predict(self.tokenizer(texts))

    # -- eval ----------------------------------------------------------------
    def eval(self, *, batches: int = 8, batch_size: int = 64,
             split: str = "dev") -> float:
        """Dev-set accuracy on the pipeline's task: classification/matching/
        tagging accuracy vs labels, next-token accuracy for LM tasks."""
        if self.task.kind != "lm":
            return eval_accuracy(self.predict, self.task, batches=batches,
                                 batch_size=batch_size, split=split)
        correct = total = 0
        for i in range(batches):
            b = get_batch(self.task, i, batch_size, split)
            pred = self.predict(b)[:, :-1]
            want = b["tokens"][:, 1:]
            correct += int((pred == want).sum())
            total += int(np.prod(want.shape))
        return correct / max(total, 1)

    # -- training hook -------------------------------------------------------
    def loss_fn(self):
        """A loss callable with the Trainer's signature
        ``(params, batch, cfg, plan, scheme, **kw)``, routed through the
        registered target head."""
        spec = self.target.spec
        if spec.name == "lm":
            return T.lm_loss

        def loss(params, batch, cfg, plan, scheme=T.QuantScheme(), **kw):
            hidden, _ = T.forward(params, batch, cfg, plan, scheme,
                                  return_hidden=True, **kw)
            return spec.loss(spec.apply(params, hidden, cfg),
                             batch["labels"])
        return loss

    def describe(self) -> str:
        from repro.distributed.sharding import mesh_fingerprint
        return (f"Pipeline[{self.cfg.name}] task={self.task.name} "
                f"target={self.target.spec.name} "
                f"policy={self.policy.describe()} "
                f"backend={self.backend.describe()} "
                f"mesh={mesh_fingerprint(self.mesh)}")
