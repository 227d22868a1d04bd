"""GraphSAGE models — the framework's flagship (bench.py drives these).

Parity: examples/graphsage (SupervisedGraphSage / UnsupervisedGraphSage /
ScalableSage) over the dense fanout path (SURVEY.md §2.3 encoders).
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Sequence

import flax.linen as nn
import jax

from euler_tpu.mp_utils.base import SuperviseModel, UnsuperviseModel
from euler_tpu.parallel.sharded_embedding import ShardedEmbedding
from euler_tpu.utils.encoders import SageEncoder, ScalableSageEncoder, ShallowEncoder

Array = jax.Array


def gather_feature_rows(batch: Dict[str, Any], rows, gather=None):
    """table[rows] for each hop's rows, honoring an int8-quantized
    table: when the batch carries 'feature_scale'
    (DeviceFeatureStore(quantize='int8')), the gathered int8 rows are
    dequantized by the per-column scale — the multiply fuses into the
    consumer, and the gather itself moves half the HBM bytes.

    A 'hub_cache' batch key (PartitionedFeatureStore: the replicated
    top-degree rows of a mesh-partitioned table) routes every feature
    gather CACHE-FIRST: rows below the cache height are served from
    the local replica and only the cold tail reaches `gather` (the
    cross-shard exchange), with hub positions masked to the trailing
    zero row — dequant applies after the combine, so int8 routing is
    byte-exact too."""
    from euler_tpu.parallel.feature_store import dequantize_rows

    table = batch["feature_table"]
    take = gather or (lambda t, r: jax.numpy.take(t, r, axis=0))
    hub = batch.get("hub_cache")
    if hub is not None:
        from euler_tpu.parallel.partitioned_store import hub_routed_take

        take = hub_routed_take(take, hub)
    scale = batch.get("feature_scale")
    out = []
    for hop, r in enumerate(rows):
        # one name per element of `rows` (hop 0: the roots); the
        # dequantise multiply stays inside it
        with jax.named_scope(f"gather/hop{hop}"):
            x = take(table, r)
            out.append(x if scale is None else dequantize_rows(x, scale))
    return out


# encoders DeviceSampledGraphSage takes, and those whose softmax masks
# pad slots (by the pad row's id, the table's last)
FANOUT_ENCODERS = ("sage", "gcn", "genie", "gat", "unimp")
_ATTENTION_ENCODERS = ("gat", "unimp")
_LABEL_STREAM = 0x1abe1  # folded into the step's key for the labels' word


def _neighbor_major(rows, fanouts, encoder: str):
    """The device draw's per-hop ids in `neighbor_major_rows`' order, for
    the encoder that is then told so (`neighbor_major=True`; the
    attention encoders read no other order). Counted at trace time, one
    a fanout whatever its hops:
    `traced_paths_total{path="neighbor_major_fanout",detail=<encoder>}`."""
    from euler_tpu import obs
    from euler_tpu.utils.encoders import neighbor_major_rows

    obs.traced_path("neighbor_major_fanout", encoder)
    return neighbor_major_rows(rows, fanouts)


def label_visible(ids, word, rate: float):
    """bool per row id: whether the step shows the node's label to the
    model. A pure function of (id, word): x = id ^ word as uint32, mixed
    by murmur3's 32-bit finaliser (x ^= x >> 16; x *= 0x85ebca6b;
    x ^= x >> 13; x *= 0xc2b2ae35; x ^= x >> 16), visible where
    x < floor(rate * 2**32) (rate 1: all but one word in 2**32). A node
    drawn twice in a step shows the same thing, another word shows
    another `rate` share, and nothing table-sized is made."""
    x = ids.astype(jax.numpy.uint32) ^ word
    x = (x ^ (x >> 16)) * jax.numpy.uint32(0x85ebca6b)
    x = (x ^ (x >> 13)) * jax.numpy.uint32(0xc2b2ae35)
    x = x ^ (x >> 16)
    return x < jax.numpy.uint32(min(int(rate * 2 ** 32), 2 ** 32 - 1))


def among_roots(ids, roots):
    """bool per id: whether it is one of `roots`. Compare-any with the
    ids on the minor axis, so the [B, n] compare fuses into its reduce
    and is never written."""
    return (roots[:, None] == ids[None, :]).any(axis=0)


def _fanout_layers(batch: Dict[str, Any]):
    """Per-hop feature arrays from either batch geometry:
      'layers'               — features shipped from the host (engine path)
      'rows' + 'feature_table' — int32 rows gathered from a device-resident
                               table (DeviceFeatureStore path; the gather
                               runs in-jit, so only ~0.7MB of rows crosses
                               the host↔device link per step)."""
    layers = batch.get("layers")
    if layers is not None:
        return layers
    return gather_feature_rows(batch, batch["rows"])


class SupervisedGraphSage(SuperviseModel):
    """Fanout batch {'layers': [x0..xL]} (or rows + device feature table)
    → SageEncoder → logits."""

    dim: int = 32
    fanouts: Sequence[int] = (10, 10)
    aggregator: str = "mean"

    def embed(self, batch: Dict[str, Any]) -> Array:
        return SageEncoder(self.dim, tuple(self.fanouts), self.aggregator,
                           name="encoder")(_fanout_layers(batch))


class UnsupervisedGraphSage(UnsuperviseModel):
    """Fanout batch + pos/negs ids → sage embedding vs context table."""

    fanouts: Sequence[int] = (10, 10)
    aggregator: str = "mean"

    def embed(self, batch: Dict[str, Any]) -> Array:
        return SageEncoder(self.dim, tuple(self.fanouts), self.aggregator,
                           concat=False, name="encoder")(_fanout_layers(batch))


class _GatherEncode(nn.Module):
    """gather + encode as ONE module — the single encoder dispatch for
    DeviceSampledGraphSage (every config shares this param tree, so the
    remat toggle never invalidates a checkpoint). Wrapped in nn.remat
    when remat=True: that puts gather+encode under one jax.checkpoint
    boundary, so the backward pass RE-GATHERS the per-hop feature
    layers instead of keeping them alive — at the canonical products
    shape the hop-2 layer alone is ~1GB bf16, the allocation that makes
    batch 65536 OOM. Residuals kept are only the HBM tables (already
    resident) and the int32 rows.

    The sampled ids are put neighbour-major before the gather, whatever
    the encoder (`_neighbor_major`): the rows gathered are the same, and
    every encoder's view of a hop by its parents' slots is then free on
    the chip where the draw's own order costs a relayout of the gathered
    features and of their cotangent."""

    dim: int
    fanouts: tuple
    aggregator: str
    encoder: str
    gather: Any = None  # make_table_gather closure for sharded tables
    heads: int = 1      # encoders 'gat' and 'unimp' only, as is out_dim
    out_dim: int = 0
    label_rate: float = 0.0  # encoder 'unimp' only
    batch_norm: bool = False  # encoder 'gat' only, as is head_dim
    head_dim: int = 0

    @nn.compact
    def __call__(self, table, scale, rows, label_in=None):
        from euler_tpu.utils.encoders import (
            GATEncoder, GCNEncoder, GenieEncoder, UniMPEncoder,
        )

        batch = {"feature_table": table}
        if scale is not None:
            batch["feature_scale"] = scale
        rows = _neighbor_major(rows, self.fanouts, self.encoder)
        layers = gather_feature_rows(batch, rows, gather=self.gather)
        if self.encoder in _ATTENTION_ENCODERS:
            # the softmax needs to know the pad slots (the mean only
            # needed the pad row's zeros); the pad row is the table's last
            pad = table.shape[0] - 1
            masks = [r != pad for r in rows]
        if self.encoder == "unimp":
            layers = self._with_labels(layers, rows, masks, *label_in)
            return UniMPEncoder(self.dim, self.fanouts, self.heads,
                                self.out_dim, name="enc")(layers, masks)
        if self.encoder == "gat":
            return GATEncoder(self.dim, self.fanouts, self.heads,
                              self.out_dim, self.batch_norm, self.head_dim,
                              name="enc")(layers, masks)
        if self.encoder == "gcn":
            return GCNEncoder(self.dim, self.fanouts, neighbor_major=True,
                              name="enc")(layers)
        if self.encoder == "genie":
            return GenieEncoder(self.dim, self.fanouts, neighbor_major=True,
                                name="enc")(layers)
        return SageEncoder(self.dim, self.fanouts, self.aggregator,
                           neighbor_major=True, name="enc")(layers)

    def _with_labels(self, layers, rows, masks, label_table, roots, word):
        """Labels as inputs (UniMP's masked label embedding; PyG's
        MaskLabel, "add"): every sampled row of hops 1..L gets its label
        row, as stored, times `label_emb/kernel` added to its features
        where the step shows that label: `label_visible` says so, and
        never for a root of this step (its label is what the loss asks
        for, also where the root is drawn again as a neighbour), nor for
        the pad row. The roots' own rows get none. Scopes
        `labelin/hop<h>`; counted at trace time, one a hop:
        `traced_paths_total{path="label_input",detail=<hop>}`."""
        from euler_tpu import obs

        emb = nn.Dense(layers[0].shape[-1], use_bias=False,
                       name="label_emb")
        out = [layers[0]]
        for hop in range(1, len(rows)):
            obs.traced_path("label_input", hop)
            with jax.named_scope(f"labelin/hop{hop}"):
                shown = (label_visible(rows[hop], word, self.label_rate)
                         & ~among_roots(rows[hop], roots) & masks[hop])
                # row ids are the program's own draws: clipped, not
                # tested and filled
                y = jax.numpy.take(label_table, rows[hop], axis=0,
                                   mode="clip")
                out.append(layers[hop] + jax.numpy.where(
                    shown[:, None], emb(y), 0.0))
        return out


class DeviceSampledGraphSage(SuperviseModel):
    """A fanout model whose sampling runs ON DEVICE (DeviceNeighborTable):
    the batch carries only root rows + a sample seed; neighbor sampling,
    feature gather, and label lookup all read HBM-resident tables inside
    the jitted step. The TPU-first configuration bench.py measures —
    the host feeder drops out of the critical path entirely. encoder
    picks any fanout-layer encoder (FANOUT_ENCODERS — all consume the
    per-hop feature list the on-device sampler produces). 'gat' is
    multi-head attention (utils/encoders.GATEncoder: `heads` heads of
    width `dim` a hidden layer) whose last layer emits the class logits
    itself, so the model has no `out` layer then. 'unimp' is UniMP
    (Shi et al. 2020): dot-product attention with a gated residual and
    LayerNorm (utils/encoders.UniMPEncoder, `heads` x `dim` as 'gat'),
    and the sampled neighbours' labels as inputs, each shown to a step
    with probability `label_rate` and never a root's own
    (`_GatherEncode._with_labels`). With 'gat', `norm="batch"` puts a
    batch normalisation between every hidden layer's skip-add and its
    ELU, its statistics taken over ALL rows the layer writes (every hop
    together; utils/encoders.HopBatchNorm) and carried as `batch_stats`
    in the train state (running ones at evaluation), and `head_dim=W`
    makes the last layer a hidden one too, followed by the classifier
    Dense(W) - norm - ReLU - Dense(num_classes): OGB-LSC's MAG240M
    baseline GAT. Feature rows of any width are gathered as stored (a
    768-byte int8 row is six lane tiles; `DeviceFeatureStore`)."""

    dim: int = 32
    fanouts: Sequence[int] = (10, 10)
    aggregator: str = "mean"
    encoder: str = "sage"
    heads: int = 4  # attention heads a layer ('gat', 'unimp')
    label_rate: float = 0.625  # share of labels a step shows ('unimp')
    # 'batch': normalised hidden layers and head ('gat')
    norm: Optional[str] = None
    head_dim: int = 0  # > 0: an MLP head of this width ('gat')
    # remat: recompute gather+encode in the backward pass
    # (_RematGatherEncode) — unlocks batches whose per-hop feature
    # layers don't fit HBM twice. Replicated tables only.
    remat: bool = False
    # uniform_sampling: the table's rows are unit-weight
    # (DeviceNeighborTable.uniform_rows — unweighted graphs) → each hop
    # is ONE neighbor-row gather, no cum-row read. Applies on the
    # replicated split-table path only (fused/row-sharded layouts keep
    # the weighted draw); distribution-identical on such tables.
    uniform_sampling: bool = False

    def embed(self, batch: Dict[str, Any]) -> Array:
        from euler_tpu.parallel.device_sampler import (
            is_model_sharded, make_table_gather, sample_fanout_rows,
            sample_fanout_rows_fused,
        )

        if self.encoder not in FANOUT_ENCODERS:
            *names, last = map(repr, FANOUT_ENCODERS)
            raise ValueError(
                f"DeviceSampledGraphSage.encoder must be "
                f"{', '.join(names)} or {last}, got {self.encoder!r}")
        if self.norm not in (None, "batch") or self.head_dim < 0:
            raise ValueError(
                "DeviceSampledGraphSage.norm must be None or 'batch' and "
                f"head_dim >= 0, got {self.norm!r} and {self.head_dim}")
        if (self.norm or self.head_dim) and self.encoder != "gat":
            raise ValueError(
                "DeviceSampledGraphSage.norm and head_dim are encoder "
                f"'gat''s, got encoder={self.encoder!r}")
        roots = batch["rows"][0]
        key = jax.random.fold_in(jax.random.key(17), batch["sample_seed"])
        # table_mesh set → tables are row-sharded over 'model' and every
        # read goes through the masked-take + psum gather; None → the
        # replicated local-take fast path
        gather = make_table_gather(self.table_mesh)
        sharded = is_model_sharded(self.table_mesh)
        if batch.get("nbrcum_table") is not None:
            # fused [N+1, 2C] layout (DeviceNeighborTable(fused=True)):
            # one row gather per hop instead of cum + neighbor gathers.
            # Composes with row-sharded tables: the gather becomes one
            # masked-take+psum per hop (half the split-sharded path's)
            rows = sample_fanout_rows_fused(batch["nbrcum_table"], roots,
                                            tuple(self.fanouts), key,
                                            gather=gather if sharded
                                            else None)
        else:
            # alias_table in the batch (DeviceNeighborTable(alias=True))
            # selects the O(1) alias draw; it subsumes the uniform
            # shortcut, so presence wins over uniform_sampling
            atab = batch.get("alias_table") if not sharded else None
            rows = sample_fanout_rows(
                batch["nbr_table"], batch["cum_table"],
                roots, tuple(self.fanouts), key,
                gather=gather if sharded else None,
                uniform=(self.uniform_sampling and not sharded
                         and atab is None),
                alias_table=atab)
        if self.remat and sharded:
            raise ValueError(
                "DeviceSampledGraphSage(remat=True) supports "
                "replicated tables only (the re-gather would nest "
                "shard_map inside jax.checkpoint)")
        if self.encoder in _ATTENTION_ENCODERS and sharded:
            raise ValueError(
                f"DeviceSampledGraphSage(encoder={self.encoder!r}) "
                "supports replicated tables only: its softmax masks pad "
                "slots by the pad row's id, taken from the table's "
                "shape, which row-sharding pads to the model-axis "
                "multiple")
        mod_cls = nn.remat(_GatherEncode) if self.remat else _GatherEncode
        mod = mod_cls(self.dim, tuple(self.fanouts), self.aggregator,
                      self.encoder, gather=gather if sharded else None,
                      heads=int(self.heads), out_dim=int(self.num_classes),
                      label_rate=float(self.label_rate),
                      batch_norm=self.norm == "batch",
                      head_dim=int(self.head_dim), name="encoder")
        label_in = None
        if self.encoder == "unimp":
            if not 0.0 <= self.label_rate <= 1.0:
                raise ValueError(
                    "DeviceSampledGraphSage.label_rate is the share of "
                    f"labels a step shows, in [0, 1]; got {self.label_rate}")
            word = jax.random.bits(jax.random.fold_in(key, _LABEL_STREAM),
                                   (), jax.numpy.uint32)
            label_in = (batch["label_table"], roots, word)
        return mod(batch["feature_table"], batch.get("feature_scale"),
                   rows, label_in)

    def logits(self, emb: Array) -> Array:
        if self.encoder in _ATTENTION_ENCODERS:
            return emb  # the last attention layer maps to the classes
        return super().logits(emb)


class DeviceSampledScalableSage(SuperviseModel):
    """Historical-activation GraphSAGE with sampling AND the activation
    cache ON DEVICE — the in-jit re-application of the reference's
    ScalableGCN/ScalableSage insight (tf_euler/python/utils/encoders.py
    :294,629, there a host-side TF variable store).

    Structural fix for the products-scale bottleneck (PERF.md): the
    canonical 2-hop fanout gathers ~B·k1·k2 random feature rows per
    step (~5M at batch 32768, fanouts [15,10]) — the dominant HBM cost.
    This model samples ONE hop, gathers raw features for roots + hop-1
    neighbors only (B + B·k rows), and reads deeper-layer neighbor
    activations from an HBM cache [N+1, dim] carried in the train
    state's 'cache' collection (donated each step → XLA updates it in
    place). Per-step gather bytes drop ~10× at the canonical shape;
    staleness is the documented ScalableGCN tradeoff, pinned by the
    graphsage-dev-cache quality row in RESULTS.md.

    Eval applies with the cache frozen (read-only), same protocol as
    the reference's store-based eval."""

    dim: int = 32
    fanout: int = 10          # neighbors sampled per node (single hop)
    num_layers: int = 2       # model depth; layers >0 read the cache
    max_id: int = 0           # cache rows - 1 == feature-table rows - 1
    cache_dtype: Any = None   # None → float32; jnp.bfloat16 at scale
    store_decay: float = 0.9  # EMA weight on the old cached activation
    encoder: str = "sage"     # 'sage' (concat) or 'gcn' (mean-combine),
    # the reference's two scalable variants (encoders.py:294,629)
    uniform_sampling: bool = False  # as DeviceSampledGraphSage

    def embed(self, batch: Dict[str, Any]) -> Array:
        import jax.numpy as jnp

        from euler_tpu.parallel.device_sampler import (
            draw_scope, is_model_sharded, make_table_gather, sample_hop,
            sample_hop_fused,
        )

        roots = batch["rows"][0]
        b = roots.shape[0]
        key = jax.random.fold_in(jax.random.key(17), batch["sample_seed"])
        gather = make_table_gather(self.table_mesh)
        tg = gather if is_model_sharded(self.table_mesh) else None
        with draw_scope(1):
            if batch.get("nbrcum_table") is not None:
                nbr = sample_hop_fused(batch["nbrcum_table"], roots,
                                       int(self.fanout), key, tg)
            else:
                atab = batch.get("alias_table") if tg is None else None
                nbr = sample_hop(batch["nbr_table"], batch["cum_table"],
                                 roots, int(self.fanout), key, tg,
                                 uniform=self.uniform_sampling
                                 and tg is None and atab is None,
                                 alias_table=atab)
        x, nbr_x = gather_feature_rows(batch, [roots, nbr], gather=gather)
        if self.encoder == "gcn":
            from euler_tpu.utils.encoders import ScalableGCNEncoder
            enc_cls = ScalableGCNEncoder
        elif self.encoder == "sage":
            enc_cls = ScalableSageEncoder
        else:
            raise ValueError(
                f"DeviceSampledScalableSage.encoder must be 'sage' or "
                f"'gcn', got {self.encoder!r}")
        enc = enc_cls(
            self.dim, int(self.num_layers), int(self.max_id),
            store_decay=self.store_decay,
            cache_dtype=self.cache_dtype or jnp.float32, name="encoder")
        return enc(roots, x, nbr.reshape(b, int(self.fanout)),
                   nbr_x.reshape(b, int(self.fanout), x.shape[-1]))


def shard_act_cache(est, mesh, axis: str = "model"):
    """Re-place the estimator's activation cache row-sharded over the
    mesh's model axis (per-chip cache bytes 1/mp — the same capacity
    lever row-sharded graph tables get from placement.put_row_sharded).
    GSPMD keeps the sharding through the jitted train step (the cache
    update is a row scatter, so each chip only writes its slice;
    pinned by tests/test_parallel.py::test_act_cache_row_sharded).
    Call once after the first train step (or any state init)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from euler_tpu.parallel.device_sampler import is_model_sharded

    if not is_model_sharded(mesh, axis):
        return
    state = est.state
    if state is None:
        # calling before the state exists is a caller bug that would
        # silently forfeit the 1/mp memory lever at scale
        raise ValueError(
            "shard_act_cache: estimator state not initialized — run at "
            "least one train step before sharding the cache")
    if "cache" not in (state.extra_vars or {}):
        return  # model carries no activation cache: legitimate no-op
    sh = NamedSharding(mesh, P(axis, None))
    cache = jax.tree_util.tree_map(
        lambda a: jax.device_put(a, sh), state.extra_vars["cache"])
    est.state = state.replace(
        extra_vars={**state.extra_vars, "cache": cache})


def refresh_act_cache(est, n_rows=None, chunk: int = 8192, seed: int = 1):
    """Full-coverage refresh of a DeviceSampledScalableSage estimator's
    activation cache: run the model forward over EVERY table row in
    chunks with the cache mutable, so nodes outside the train split get
    populated entries too (first writes land at full scale —
    encoders._ema_update). This is the structural fix for the config's
    quality gap on small-train-split data: plain training only ever
    writes cache rows for train roots, so eval-time neighbor reads hit
    zeros. Install as `est.pre_eval_hook = refresh_act_cache` (the
    reference's analog is its periodic full-graph store refresh in the
    ScalableGCN training loop, tf_euler/python/utils/encoders.py:294).

    The trailing pad row is excluded and re-zeroed: padded neighbor
    slots must keep aggregating zeros, not relu(bias)."""
    import numpy as np

    state = est.state
    if not (state and state.extra_vars
            and "cache" in (state.extra_vars or {})):
        return
    cache = state.extra_vars["cache"]
    if n_rows is None:
        n_rows = int(est.static_batch["feature_table"].shape[0])
    live = n_rows - 1  # rows 0..live-1 are real nodes; row live is pad
    chunk = max(1, min(chunk, live))

    upd = getattr(est, "_act_cache_upd", None)
    if upd is None:
        # memoized on the estimator: a fresh jax.jit wrapper per call
        # would recompile at every pre-eval refresh. Capture ONLY
        # apply_fn (a constant holding no arrays) — closing over the
        # whole TrainState would pin the first call's params+opt_state
        # copy in device memory for the estimator's lifetime
        apply_fn = state.apply_fn

        @jax.jit
        def upd(params, cache, batch):
            _, new = apply_fn({"params": params, "cache": cache},
                              batch, mutable=["cache"])
            return new["cache"]

        est._act_cache_upd = upd

    import jax.numpy as jnp

    base = dict(est.static_batch)
    for i, lo in enumerate(range(0, live, chunk)):
        rows = np.arange(lo, lo + chunk, dtype=np.int32)
        rows = np.minimum(rows, live - 1)  # tail clamps to a real row
        batch = {**base, "rows": [jnp.asarray(rows)],
                 "sample_seed": np.uint32(seed * 1_000_003 + i)}
        cache = upd(state.params, cache, batch)
    cache = jax.tree_util.tree_map(
        lambda a: a.at[live].set(jnp.zeros((), a.dtype)), cache)
    est.state = state.replace(
        extra_vars={**state.extra_vars, "cache": cache})


class DeviceSampledLayerwiseGCN(SuperviseModel):
    """FastGCN/LADIES with sampling ON DEVICE: per-layer importance
    pools, dense inter-pool adjacency, and feature gathers all run
    in-jit over the HBM tables (parallel/device_layerwise.py); the host
    ships root rows + a seed. Reference topology: API_SAMPLE_L
    (sample_layer_op.cc:74) + LayerwiseDataFlow on the host."""

    dim: int = 32
    layer_sizes: Sequence[int] = (128, 128)
    # per-layer input dropout inside LayerEncoder (the standard FastGCN
    # setup) — distinct from SuperviseModel.dropout, which the base
    # class applies once to the final embedding
    layer_dropout: float = 0.0

    def embed(self, batch: Dict[str, Any]) -> Array:
        from euler_tpu.parallel.device_layerwise import sample_layerwise_rows
        from euler_tpu.utils.encoders import LayerEncoder

        if batch.get("adjs") is not None:
            # host-built layerwise batch (NodeEstimator eval_via_flow):
            # the FastGCN protocol evaluates on exact 1-hop closures, so
            # eval geometry arrives from the host flow pre-assembled
            return LayerEncoder(self.dim, dropout=self.layer_dropout,
                                name="encoder")(batch["layers"],
                                                batch["adjs"])
        if batch.get("nbrcum_table") is not None:
            raise ValueError(
                "DeviceSampledLayerwiseGCN needs the split nbr/cum "
                "tables (pool weights come from the cum rows) — build "
                "DeviceNeighborTable with fused=False")
        from euler_tpu.parallel.device_sampler import is_model_sharded

        if is_model_sharded(self.table_mesh):
            raise NotImplementedError(
                "row-sharded tables are not supported for device "
                "layerwise sampling (top-k pooling needs the full "
                "candidate slot set) — use replicated tables "
                "(shard_rows=False)")
        roots = batch["rows"][0]
        key = jax.random.fold_in(jax.random.key(31), batch["sample_seed"])
        levels, adjs = sample_layerwise_rows(
            batch["nbr_table"], batch["cum_table"], roots,
            tuple(self.layer_sizes), key,
            alias_table=batch.get("alias_table"))
        layers = gather_feature_rows(batch, levels)
        return LayerEncoder(self.dim, dropout=self.layer_dropout,
                            name="encoder")(layers, adjs)


class DeviceSampledUnsupervisedSage(nn.Module):
    """Unsupervised GraphSAGE fully on device: the fanout embedding AND
    the positive/negative context pipeline run in-jit. Positives are one
    weighted neighbor draw per root (the reference's SamplePosWithTypes
    role, solution/samplers.py); negatives draw from the HBM node-weight
    sampler (DeviceNodeSampler). The host ships only root rows + a seed.
    Pairs whose positive lands on pad_row (isolated roots) are masked
    out of loss and metric."""

    num_rows: int = 0
    dim: int = 32
    fanouts: Sequence[int] = (10, 10)
    aggregator: str = "mean"
    num_negs: int = 5
    # set to the mesh when the nbr/cum (or fused) + feature tables are
    # row-sharded over 'model' (shard_rows=True): every table read then
    # goes through the masked-take+psum gather. The negative-sampler
    # tables (neg_rows/neg_cum) stay replicated — they are O(N) scalars,
    # not O(N·C)/O(N·D) rows.
    table_mesh: Any = None
    uniform_sampling: bool = False  # as DeviceSampledGraphSage

    @nn.compact
    def __call__(self, batch: Dict[str, Any]):
        import jax.numpy as jnp
        import optax

        from euler_tpu.mp_utils.base import ModelOutput
        from euler_tpu.parallel.device_sampler import (
            is_model_sharded, make_table_gather, sample_fanout_rows,
            sample_fanout_rows_fused, sample_hop, sample_hop_fused,
        )
        from euler_tpu.parallel.device_walk import sample_global_rows
        from euler_tpu.utils import metrics as M
        from euler_tpu.utils.layers import Embedding

        roots = batch["rows"][0]
        pad = self.num_rows
        key = jax.random.fold_in(jax.random.key(29), batch["sample_seed"])
        kf, kp, kn = jax.random.split(key, 3)
        gather = make_table_gather(self.table_mesh)
        tg = gather if is_model_sharded(self.table_mesh) else None
        fused_tab = batch.get("nbrcum_table")
        if fused_tab is not None:
            rows = sample_fanout_rows_fused(fused_tab, roots,
                                            tuple(self.fanouts), kf,
                                            gather=tg)
        else:
            atab = batch.get("alias_table") if tg is None else None
            unif = self.uniform_sampling and tg is None and atab is None
            rows = sample_fanout_rows(batch["nbr_table"],
                                      batch["cum_table"],
                                      roots, tuple(self.fanouts), kf,
                                      gather=tg, uniform=unif,
                                      alias_table=atab)
        rows = _neighbor_major(rows, tuple(self.fanouts), "sage")
        layers = gather_feature_rows(batch, rows, gather=gather)
        emb = SageEncoder(self.dim, tuple(self.fanouts), self.aggregator,
                          concat=False, neighbor_major=True,
                          name="encoder")(layers)                 # [B, D]
        with jax.named_scope("draw/pos"):
            if fused_tab is not None:
                pos_r = sample_hop_fused(fused_tab, roots, 1, kp, tg)
            else:
                pos_r = sample_hop(batch["nbr_table"], batch["cum_table"],
                                   roots, 1, kp, gather=tg,
                                   uniform=self.uniform_sampling
                                   and tg is None and atab is None,
                                   alias_table=atab)              # [B]
        negs_r = sample_global_rows(batch["neg_rows"], batch["neg_cum"],
                                    kn, (roots.shape[0], self.num_negs))
        ctx = Embedding(self.num_rows + 1, self.dim, name="ctx_emb")
        pos = ctx(pos_r)                                          # [B, D]
        negs = ctx(negs_r)                                        # [B, N, D]
        pos_logit = (emb * pos).sum(-1, keepdims=True)
        neg_logit = jnp.einsum("bd,bnd->bn", emb, negs)
        valid = (pos_r != pad).astype(jnp.float32)
        loss = (
            M.masked_mean(optax.sigmoid_binary_cross_entropy(
                pos_logit, jnp.ones_like(pos_logit)).mean(-1), valid)
            + M.masked_mean(optax.sigmoid_binary_cross_entropy(
                neg_logit, jnp.zeros_like(neg_logit)).mean(-1), valid)
        )
        scores = jnp.concatenate([pos_logit, neg_logit], axis=1)
        ranks = 1.0 + (scores[:, 1:] >= scores[:, :1]).sum(
            axis=1).astype(jnp.float32)
        mrr = M.masked_mean(1.0 / ranks, valid)
        return ModelOutput(emb, loss, "mrr", mrr)


class ShardedSupervisedGraphSage(SuperviseModel):
    """GraphSAGE with an id-embedding input sharded across the mesh's
    'model' axis — the multi-chip flagship: feature = concat(sharded id
    embedding, dense features). Exercises DP (batch) + embedding MP in one
    step, the SURVEY §2.4 mapping."""

    dim: int = 32
    fanouts: Sequence[int] = (10, 10)
    aggregator: str = "mean"
    max_id: int = 0
    id_dim: int = 16

    def embed(self, batch: Dict[str, Any]) -> Array:
        emb = ShardedEmbedding(self.max_id + 1, self.id_dim, name="id_emb")
        layers = []
        for ids, x in zip(batch["ids"], batch["layers"]):
            e = emb(ids)
            layers.append(jax.numpy.concatenate([x, e], axis=-1))
        return SageEncoder(self.dim, tuple(self.fanouts), self.aggregator,
                           name="encoder")(layers)


class ScalableGraphSage(SuperviseModel):
    """1-hop sampling + historical activation caches (reference
    ScalableSageEncoder). Run with mutable=['cache']."""

    dim: int = 32
    num_layers: int = 2
    max_id: int = 0

    def embed(self, batch: Dict[str, Any]) -> Array:
        enc = ScalableSageEncoder(self.dim, self.num_layers, self.max_id,
                                  name="encoder")
        ids = batch["ids"][0]
        x = batch["layers"][0]
        nbr_ids = batch["ids"][1].reshape(ids.shape[0], -1)
        nbr_x = batch["layers"][1].reshape(ids.shape[0], -1, x.shape[-1])
        return enc(ids, x, nbr_ids, nbr_x)
