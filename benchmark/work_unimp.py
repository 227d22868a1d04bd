"""What one training step of the UniMP configuration has to compute and
to move, from shapes alone, by the rules of work.py.

The function is named `sage` for the fanout draw it counts under, as
work_gat.py's is. The table kernels' row counts are not here: this
model's draw and feature gather are the fanout ones, so its
configuration names `kernel_work.sage` under `kernels`.

FLOPs, 2 m k n a matrix product forward: the label embedding
`onehot(y) W_d` of every sampled row of hops 1..L; a layer's four
projections (query and skip of its targets, key and value of its
sources); the two attention contractions a pair (the scores `q . k` and
the sum `alpha . v` over the k slots). The backward pass doubles what
has a gradient of its input and adds once what has not, as in work.py:
the label rows and the roots' feature rows are data, every other input
carries the label embedding's gradient. Softmax, the gate, LayerNorm,
ReLU and the masks are left out.

Bytes: work.py's (neighbour rows drawn from, feature rows as stored,
the roots' label rows, parameters with gradient and Adam's moments read
and written once) plus the label rows of hops 1..L as stored (float32
one-hot), every query, key and value row written once and read once by
the forward and once by the backward pass (as work_gat.py's projected
rows), and every skip's output written once and read once.

For the per-layer metrics (`unimp_readers.py`): `labelin_bytes` (the
label rows of hops 1..L as stored), `qkv_flops` (the four projections
alone) and `dotattn_bytes` = each key row and each value row of a pair
read once, its queries read, its output written, and the same again
for the backward pass. Each is what the algorithm must move whatever
implements it, so no share of a peak read from them can pass 100 %.
"""

from __future__ import annotations

from .work import _F32, _optimizer, _tables


def _layers(cfg: dict, batch: int):
    """For each layer: (d_in, projected width H*C, output width, rows of
    each hop it reads, whether it is the first, whether the last)."""
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
                    heads * c, c if last else heads * c,
                    hops[:len(fanouts) - depth + 1], depth == 0, last))
    return hops, out


def sage(cfg: dict, batch: int, weighted: bool) -> dict:
    classes, feat = cfg["num_classes"], cfg["feature_dim"]
    hops, layers = _layers(cfg, batch)
    label_rows = sum(hops[1:])
    labelin_bytes = label_rows * classes * _F32
    label_flops = 2 * label_rows * classes * feat * 2
    n_params = classes * feat
    qkv_flops = attn_flops = act = dotattn_bytes = 0
    for d_in, zw, d_out, rows, first, last in layers:
        for hop, n in enumerate(rows):
            # the roots' feature rows are data; every other input is not
            passes = 2 if first and hop == 0 else 3
            if hop < len(rows) - 1:                   # a target: q, skip
                qkv_flops += 2 * n * d_in * (zw + d_out) * passes
                act += n * (zw * 3 + d_out * 2) * _F32
            if hop > 0:                               # a source: k, v
                qkv_flops += 2 * n * d_in * 2 * zw * passes
                act += n * 2 * zw * 3 * _F32
        for targets, sources in zip(rows[:-1], rows[1:]):
            attn_flops += 3 * 2 * (2 * sources * zw)
            dotattn_bytes += 2 * (2 * sources * zw + targets * zw
                                  + targets * d_out) * _F32
        # query, key, value, skip, the gate's vector, the norm's two
        n_params += 3 * (d_in * zw + zw) + d_in * d_out + d_out \
            + 3 * d_out + (0 if last else 2 * d_out)
    moved = _tables(cfg, sum(hops[:-1]), sum(hops), batch, weighted)
    return {"flops": label_flops + qkv_flops + attn_flops,
            "bytes": moved + labelin_bytes + act + _optimizer(n_params),
            "labelin_bytes": labelin_bytes, "qkv_flops": qkv_flops,
            "dotattn_bytes": dotattn_bytes}
