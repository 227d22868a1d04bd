"""Model contract: (embedding, loss, metric_name, metric).

Parity: tf_euler/python/mp_utils/base.py:24-90 (SuperviseModel /
UnsuperviseModel). Models are flax modules taking a batch dict (jnp
arrays, already on device) and returning a ModelOutput; the estimator
differentiates through .loss.
"""

from __future__ import annotations

from typing import Any, Dict, NamedTuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import optax

from euler_tpu.utils import metrics as M
from euler_tpu.utils.layers import Embedding

Array = jax.Array


class ModelOutput(NamedTuple):
    embedding: Array
    loss: Array
    metric_name: str
    metric: Array


class SuperviseModel(nn.Module):
    """Supervised node classification: embed → dense logits → xent.

    Subclasses define embed(batch) → [B, D]. multilabel chooses sigmoid
    cross-entropy + micro-F1 (the reference's default for cora-style
    multilabel targets, mp_utils/base.py:24-48), else softmax + accuracy.
    """

    num_classes: int = 0
    multilabel: bool = True
    # regularization (reference models use dropout 0.5 + L2 on citation
    # sets, e.g. examples/gat/gat.py): active only when the estimator
    # provides a "dropout" rng, i.e. during training steps
    dropout: float = 0.0
    # mesh whose 'model' axis row-shards the HBM tables (feature/label/
    # neighbor) — None means replicated tables, plain local gathers
    table_mesh: Any = None

    def embed(self, batch: Dict[str, Any]) -> Array:
        raise NotImplementedError

    def table_gather(self):
        from euler_tpu.parallel.device_sampler import make_table_gather

        return make_table_gather(self.table_mesh)

    def logits(self, emb: Array) -> Array:
        """[B, D] embedding -> [B, num_classes] logits: a dense `out`
        layer. A subclass whose embedding already is the class logits
        (an encoder whose last layer maps to the classes) returns it."""
        return nn.Dense(self.num_classes, name="out")(emb)

    @nn.compact
    def __call__(self, batch: Dict[str, Any]) -> ModelOutput:
        emb = self.embed(batch)
        if self.dropout > 0.0:
            emb = nn.Dropout(self.dropout)(
                emb, deterministic=not self.has_rng("dropout"))
        labels = batch.get("labels")
        if labels is None:
            # device-resident label table (DeviceFeatureStore): gather the
            # root rows in-jit instead of shipping labels from the host
            with jax.named_scope("labels"):
                labels = self.table_gather()(batch["label_table"],
                                             batch["rows"][0])
        logits = self.logits(emb)
        # optional [B] 0/1 metric_mask: padded rows (deterministic eval
        # sweeps pad the final chunk to the static batch shape) drop out
        # of both the loss mean and the metric counts
        mask = batch.get("metric_mask")

        def wmean(per_row):
            return M.masked_mean(per_row, mask)

        with jax.named_scope("loss"):
            if self.multilabel:
                loss = wmean(optax.sigmoid_binary_cross_entropy(
                    logits, labels.astype(jnp.float32)).sum(-1))
                metric = M.micro_f1(jax.nn.sigmoid(logits), labels,
                                    mask=mask)
                name = "f1"
            else:
                # labels arrive either as integer classes [B] or one-hot
                # [B, C] (dense label features are stored one-hot)
                if labels.ndim == logits.ndim:
                    loss = wmean(optax.softmax_cross_entropy(
                        logits, labels.astype(jnp.float32)))
                    int_labels = jnp.argmax(labels, axis=-1)
                else:
                    int_labels = labels.astype(jnp.int32)
                    loss = wmean(
                        optax.softmax_cross_entropy_with_integer_labels(
                            logits, int_labels))
                metric = M.micro_f1(logits, int_labels, mask=mask)
                name = "f1"
        return ModelOutput(emb, loss, name, metric)


class UnsuperviseModel(nn.Module):
    """Unsupervised embedding with negative sampling: positive (src, pos)
    pairs + num_negs sampled negatives, sigmoid ranking loss, MRR metric.

    Parity: mp_utils/base.py:49-90. Subclasses define embed(batch) and
    may override context_embed(pos, negs) -> (pos_emb, negs_emb)
    (the default embeds both from ONE shared id-context table — a single
    submodule, created once).
    batch: src_emb inputs + 'pos' ids + 'negs' ids handled by the caller's
    dataflow; this base consumes precomputed embeddings:
      embed(batch) → [B, D]; embed_context on pos [B, 1, D] / negs [B, N, D].
    """

    dim: int = 0
    max_id: int = 0
    num_negs: int = 5

    def embed(self, batch: Dict[str, Any]) -> Array:
        raise NotImplementedError

    def context_embed(self, pos: Array, negs: Array):
        """Context (pos, negs) embeddings from ONE shared table — a
        single submodule construction, since flax forbids creating two
        modules under the same explicit name in one call. Overrides
        needing more of the batch can read it in embed()/__call__."""
        ctx = Embedding(self.max_id + 1, self.dim, name="ctx_emb")
        return ctx(pos), ctx(negs)

    @nn.compact
    def __call__(self, batch: Dict[str, Any]) -> ModelOutput:
        emb = self.embed(batch)                       # [B, D]
        pos, negs = self.context_embed(batch["pos"], batch["negs"])
        if pos.ndim == 2:
            pos = pos[:, None, :]                     # [B, 1, D]
        pos_logit = jnp.einsum("bd,bkd->bk", emb, pos)    # [B, 1]
        neg_logit = jnp.einsum("bd,bkd->bk", emb, negs)   # [B, N]
        loss = (
            optax.sigmoid_binary_cross_entropy(
                pos_logit, jnp.ones_like(pos_logit)).mean()
            + optax.sigmoid_binary_cross_entropy(
                neg_logit, jnp.zeros_like(neg_logit)).mean()
        )
        scores = jnp.concatenate([pos_logit, neg_logit], axis=1)
        return ModelOutput(emb, loss, "mrr", M.mrr(scores))
