"""What one training step of the GraphSAGE-LSTM configuration has to
compute and to move, from shapes alone, by the rules of work.py.

The function is named `sage` for the fanout draw it counts under, as
work_gat.py's is. The table kernels' row counts are not here: this
model's draw and feature gather are the fanout ones, so its
configuration names `kernel_work.sage` under `kernels`.

Layer l runs, for every pair (n targets of hop h, their k slots of hop
h+1), an LSTM of width H over the k slots: a slot-row's gates are
`[x, h] K`, K [D_l + H, 4H]. Then `x S` (the targets, [D_l, dim]) and
`h_k N` ([H, dim]), and after the last layer `out` on the roots.

`lstm_flops`, the recurrence's, a FLOOR that no implementation can go
under: forward, the input product of every slot-row (2 D_l 4H) and the
recurrent one of every slot-row but a target's first (2 H 4H: h_0 is
0); backward, the kernel's gradient of the same products, the recurrent
input's gradient of the same rows, and the slots' input gradient where
the input is not data (layer 0's slots are feature rows: none).
`lstm_bytes`, likewise a floor: every slot's input row read once
forward and once backward at the width the recurrence is handed (layer
0's as stored, int8, since the dequantise can fuse into the read; the
later layers' float32), the final h of every target written, the slots'
input cotangent written where it is not data, and the kernel and bias
read with their gradient written. Gates, carries and the per-slot
residuals a fused kernel could keep on chip are left out, so neither
share read from these counts can pass 100 %.

The whole step (`flops`, `bytes`, for step_mfu_pct / step_hbm_pct):
`lstm_flops` plus the S, N and `out` products as work.py counts a Dense
(twice the forward where the input is data, else three times); bytes
are work.py's tables, each Dense output written and read once, the
parameters with gradient and Adam's moments, plus `lstm_bytes`.
"""

from __future__ import annotations

from .work import _F32, _STORED, _dense, _optimizer, _tables


def sage(cfg: dict, batch: int, weighted: bool) -> dict:
    kw = cfg["model"]["kwargs"]
    dim, hidden, fanouts = kw["dim"], cfg["lstm_hidden"], kw["fanouts"]
    gates = 4 * hidden
    hops = [batch]
    for k in fanouts:
        hops.append(hops[-1] * k)
    lstm_flops = lstm_bytes = flops = act = n_params = 0
    for depth in range(len(fanouts)):
        data = depth == 0
        width = cfg["feature_dim"] if data else 2 * dim
        # every pair (n targets of hop h, their k slots of hop h+1)
        for n, k in zip(hops[:len(fanouts) - depth], fanouts):
            x_prod = 2 * n * k * width * gates
            h_prod = 2 * n * (k - 1) * hidden * gates
            # forward; dK of both; dh; dx where the slots are not data
            lstm_flops += x_prod + h_prod + x_prod + h_prod + h_prod \
                + (0 if data else x_prod)
            row_bytes = width * (_STORED[cfg["feature_storage"]] if data
                                 else _F32)
            lstm_bytes += 2 * n * k * row_bytes + n * hidden * _F32 \
                + (0 if data else n * k * width * _F32)
            for d_in, is_data in ((width, data), (hidden, False)):
                f, a = _dense(n, d_in, dim, is_data)
                flops, act = flops + f, act + a
        lstm_params = (width + hidden) * gates + gates
        lstm_bytes += lstm_params * _F32 * 2
        n_params += lstm_params + width * dim + hidden * dim
    f, a = _dense(batch, 2 * dim, cfg["num_classes"], False)
    flops, act = flops + f, act + a
    n_params += 2 * dim * cfg["num_classes"] + cfg["num_classes"]
    moved = _tables(cfg, sum(hops[:-1]), sum(hops), batch, weighted)
    return {"flops": flops + lstm_flops,
            "bytes": moved + act + lstm_bytes + _optimizer(n_params),
            "lstm_flops": lstm_flops, "lstm_bytes": lstm_bytes}
