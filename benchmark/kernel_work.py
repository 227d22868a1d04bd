"""What the two table kernels of one training step have to read, from
shapes alone: rows and the bytes of a row as stored. The whole step's
counts are work.py's; these are its `_tables` term taken apart, one
entry a kernel, for the per-kernel shares of the HBM peak.

gather: every feature row of every hop (the roots' too), as stored.
draw: one neighbour row (cap int32 slots) for every node drawn from,
i.e. every hop but the last, plus its cumulative-weight row (cap
float32) where the edges are weighted.

Each function is named as the configuration's `work` function is
(`for_config` picks it by that name), takes what that one takes and
returns {"gather": (rows, row_bytes), "draw": (rows, row_bytes)}.
"""

from __future__ import annotations

from .work import _F32, _I32, _STORED


def _kernels(cfg, hops, weighted: bool) -> dict:
    cap = cfg["cap"]
    return {
        "gather": (sum(hops),
                   cfg["feature_dim"] * _STORED[cfg["feature_storage"]]),
        "draw": (sum(hops[:-1]),
                 cap * _I32 + (cap * _F32 if weighted else 0)),
    }


def sage(cfg: dict, batch: int, weighted: bool) -> dict:
    hops = [batch]
    for k in cfg["model"]["kwargs"]["fanouts"]:
        hops.append(hops[-1] * k)
    return _kernels(cfg, hops, weighted)


def scalablesage(cfg: dict, batch: int, weighted: bool) -> dict:
    return _kernels(
        cfg, [batch, batch * cfg["model"]["kwargs"]["fanout"]], weighted)


def for_config(cfg: dict):
    """The function here that bears the name of cfg["work"]'s."""
    return globals()[cfg["work"].rpartition(".")[2]]
