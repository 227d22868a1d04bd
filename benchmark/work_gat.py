"""What one training step of the graph-attention configuration has to
compute and to move, from shapes alone, by the rules of work.py.

The function is named `sage` for the fanout draw it counts under. The
table kernels' row counts are not here: this model's draw and feature
gather are the fanout ones, so its configuration names
`kernel_work.sage` under `kernels`.

FLOPs: the projections `x W` of every hop a layer reads (once a hop),
the linear skips of its targets, and the two attention contractions a
pair (the scores `a . z`: the sources' `a_src`, the targets' `a_src` and
`a_dst`; the sum `alpha . z` over the k slots and the target), each
2 m k n forward; the backward pass doubles what has a gradient of its
input and adds once what has not (the first layer's input is data), as
in work.py. Softmax, LeakyReLU, ELU and the mask are left out.

Bytes: work.py's (neighbour rows drawn from, feature rows as stored, the
roots' label rows, every linear skip's output written once and read
once, parameters with gradient and Adam's moments read and written once)
plus every projected row written once and read once by the forward and
once by the backward pass.

`proj_flops` (projections and skips alone) and `attn_bytes` are the
per-layer metrics' (`gat_readers.py`): attn_bytes = each projected
source row and each target's read twice (for the scores, for the sum),
the pair's output written, and the same again for the backward pass.
"""

from __future__ import annotations

from .work import _F32, _optimizer, _tables


def _layers(cfg: dict, batch: int):
    """For each layer: (d_in, one head's width, projected width, output
    width, rows of each hop it reads, whether its input is data)."""
    kw = cfg["model"]["kwargs"]
    dim, heads, fanouts = kw["dim"], kw["heads"], kw["fanouts"]
    hops = [batch]
    for k in fanouts:
        hops.append(hops[-1] * k)
    out = []
    for depth in range(len(fanouts)):
        last = depth == len(fanouts) - 1
        c = cfg["num_classes"] if last else dim
        out.append((cfg["feature_dim"] if depth == 0 else heads * dim,
                    c, heads * c, c if last else heads * c,
                    hops[:len(fanouts) - depth + 1], depth == 0))
    return hops, out


def sage(cfg: dict, batch: int, weighted: bool) -> dict:
    heads = cfg["model"]["kwargs"]["heads"]
    hops, layers = _layers(cfg, batch)
    proj_flops = attn_flops = act = attn_bytes = n_params = 0
    for d_in, c, zw, d_out, rows, is_data in layers:
        passes = 2 if is_data else 3
        proj_flops += 2 * sum(rows) * d_in * zw * passes
        proj_flops += 2 * sum(rows[:-1]) * d_in * d_out * passes
        # z written once, read by the forward and by the backward pass
        act += sum(rows) * zw * _F32 * 3
        # the skip's output, as a Dense output of work.py
        act += sum(rows[:-1]) * d_out * _F32 * 2
        for targets, sources in zip(rows[:-1], rows[1:]):
            scores = 2 * (sources + 2 * targets) * zw
            summed = 2 * (sources + targets) * zw
            attn_flops += 3 * (scores + summed)
            attn_bytes += 2 * (2 * (sources + targets) * zw
                               + targets * d_out) * _F32
        n_params += d_in * zw + 2 * c * heads + d_out \
            + d_in * d_out + d_out
    moved = _tables(cfg, sum(hops[:-1]), sum(hops), batch, weighted)
    return {"flops": proj_flops + attn_flops,
            "bytes": moved + act + _optimizer(n_params),
            "proj_flops": proj_flops, "attn_bytes": attn_bytes}
