"""What one training step has to compute and to move, from shapes alone.

FLOPs: the matrix products of the forward and backward passes (2 m k n
each). A layer whose input is data (the features) needs no gradient of
its input, so it counts twice its forward and the others three times;
nothing recomputed counts. Elementwise work, the draws' compares and the
reductions are left out: they are not what the 197 TFLOP/s are a peak of.

Bytes: what the algorithm has to read or write in HBM whatever
implements it: the neighbour rows drawn from (and the cumulative-weight
rows where edges are weighted), the feature rows gathered as stored, the
roots' label rows as stored, cache rows read and written, every Dense
layer's output written once and read once by the backward pass, and the
parameters with their gradient and Adam's two moments read and written
once. Temporaries a better schedule would not make are left out, so the
share says how far the step is from moving only what it must.

Each function takes the configuration, the root batch and whether the
edges are weighted, and returns {"flops": ..., "bytes": ...} of one step.
"""

from __future__ import annotations

_F32, _I32 = 4, 4
_STORED = {"int8": 1, "bfloat16": 2, "float32": 4}


def _dense(rows: int, k: int, n: int, input_is_data: bool):
    fwd = 2 * rows * k * n
    return fwd * (2 if input_is_data else 3), rows * n * _F32 * 2


def _tables(cfg, draw_rows: int, feature_rows: int, batch: int,
            weighted: bool) -> int:
    cap = cfg["cap"]
    per_draw_row = cap * _I32 + (cap * _F32 if weighted else 0)
    return (draw_rows * per_draw_row
            + feature_rows * cfg["feature_dim"]
            * _STORED[cfg["feature_storage"]]
            + batch * cfg["num_classes"] * _F32)


def _optimizer(n_params: int) -> int:
    # parameter, gradient, first and second moment: read and written
    return n_params * _F32 * 4 * 2


def sage(cfg: dict, batch: int, weighted: bool) -> dict:
    kw = cfg["model"]["kwargs"]
    dim, fanouts = kw["dim"], kw["fanouts"]
    hops = [batch]
    for k in fanouts:
        hops.append(hops[-1] * k)
    flops = act = n_params = 0
    for depth in range(len(fanouts)):
        width = cfg["feature_dim"] if depth == 0 else 2 * dim
        for hop in range(len(fanouts) - depth):
            for _ in ("self", "nbr"):
                f, a = _dense(hops[hop], width, dim, depth == 0)
                flops, act = flops + f, act + a
        n_params += 2 * (width * dim + dim)
    f, a = _dense(batch, 2 * dim, cfg["num_classes"], False)
    flops, act = flops + f, act + a
    n_params += 2 * dim * cfg["num_classes"] + cfg["num_classes"]
    moved = _tables(cfg, sum(hops[:-1]), sum(hops), batch, weighted)
    return {"flops": flops, "bytes": moved + act + _optimizer(n_params)}


def scalablesage(cfg: dict, batch: int, weighted: bool) -> dict:
    kw = cfg["model"]["kwargs"]
    dim, k, layers = kw["dim"], kw["fanout"], kw["num_layers"]
    cache_b = _STORED[kw["cache_dtype"]]
    flops = act = n_params = cache = 0
    for layer in range(layers):
        width = cfg["feature_dim"] if layer == 0 else dim
        f, a = _dense(batch, 2 * width, dim, layer == 0)
        flops, act = flops + f, act + a
        n_params += 2 * width * dim + dim
        if layer > 0:
            # neighbours' rows read; the roots' rows read and written
            cache += (batch * k + 2 * batch) * dim * cache_b
    f, a = _dense(batch, dim, cfg["num_classes"], False)
    flops, act = flops + f, act + a
    n_params += dim * cfg["num_classes"] + cfg["num_classes"]
    moved = _tables(cfg, batch, batch * (1 + k), batch, weighted)
    return {"flops": flops,
            "bytes": moved + cache + act + _optimizer(n_params)}
