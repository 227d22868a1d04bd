"""What one training step of the MAG240M baseline GAT has to compute and
to move, from shapes alone, by the rules of work.py and work_gat.py.

The function is named `sage` for the fanout draw it counts under, as
`work_gat.py`'s is. The table kernels' row counts are not here: this
model's draw and feature gather are the fanout ones, so its
configuration names `kernel_work.sage` under `kernels` (a feature row
then counts `feature_dim` stored bytes: 768).

Every layer is a hidden one (heads concatenated, `heads * dim` wide) and
an MLP head `Dense(head_dim) - norm - ReLU - Dense(classes)` follows on
the roots. FLOPs: the projections `x W` of every hop a layer reads (once
a hop), the linear skips of its targets, the head's two products, and
the two attention contractions a pair, each 2 m k n forward; the
backward pass doubles what has a gradient of its input and adds once
what has not (the first layer's input is data). Softmax, LeakyReLU, ELU,
the mask and the norms' arithmetic are left out.

Bytes: work_gat.py's (tables as stored, projected rows written once and
read by both passes, skip and head outputs written and read once,
parameters with gradient and Adam's moments) plus `norm_bytes`: every
normalised row read and written once forward, its cotangent and itself
read and its input's cotangent written backward (5 passes of 4 bytes).

`proj_flops` (projections, skips and the head's products), `attn_bytes`
(each projected source row and each target's read twice, the pair's
output written, the same again backward) and `norm_bytes` are the
per-layer metrics' (`mag_readers.py`).
"""

from __future__ import annotations

from .work import _F32, _dense, _optimizer, _tables

_NORM_PASSES = 5


def sage(cfg: dict, batch: int, weighted: bool) -> dict:
    kw = cfg["model"]["kwargs"]
    heads, fanouts, head_dim = kw["heads"], kw["fanouts"], kw["head_dim"]
    wide = heads * kw["dim"]
    hops = [batch]
    for k in fanouts:
        hops.append(hops[-1] * k)
    proj_flops = attn_flops = act = attn_bytes = norm_rows = n_params = 0
    for depth in range(len(fanouts)):
        d_in = cfg["feature_dim"] if depth == 0 else wide
        rows = hops[:len(fanouts) - depth + 1]
        passes = 2 if depth == 0 else 3
        proj_flops += 2 * sum(rows) * d_in * wide * passes
        proj_flops += 2 * sum(rows[:-1]) * d_in * wide * passes
        # z written once, read by the forward and by the backward pass;
        # the skip's output as a Dense output of work.py
        act += sum(rows) * wide * _F32 * 3 + sum(rows[:-1]) * wide * _F32 * 2
        for targets, sources in zip(rows[:-1], rows[1:]):
            scores = 2 * (sources + 2 * targets) * wide
            summed = 2 * (sources + targets) * wide
            attn_flops += 3 * (scores + summed)
            attn_bytes += 2 * (2 * (sources + targets) * wide
                               + targets * wide) * _F32
        norm_rows += sum(rows[:-1]) * wide
        # projection, two attention vectors, bias, skip, the norm's two
        n_params += d_in * wide + 2 * wide + wide + d_in * wide + wide \
            + 2 * wide
    for k, n in ((wide, head_dim), (head_dim, cfg["num_classes"])):
        f, a = _dense(batch, k, n, False)
        proj_flops, act = proj_flops + f, act + a
        n_params += k * n + n
    norm_rows += batch * head_dim
    n_params += 2 * head_dim
    norm_bytes = norm_rows * _F32 * _NORM_PASSES
    moved = _tables(cfg, sum(hops[:-1]), sum(hops), batch, weighted)
    return {"flops": proj_flops + attn_flops,
            "bytes": moved + act + norm_bytes + _optimizer(n_params),
            "proj_flops": proj_flops, "attn_bytes": attn_bytes,
            "norm_bytes": norm_bytes}
