"""Per-layer metrics of the UniMP encoder, read from the scopes its
label input and its layers carry in the one trace
(`euler_tpu/models/graphsage._GatherEncode._with_labels`:
`.../encoder/labelin/hop<h>`; `euler_tpu/utils/encoders.
TransformerConvLayer`: `.../encoder/enc/layer<l>/qkv`, `/attn`,
`/gate`). The trace is loaded and its compiler-made operations named by
`scope_readers`; the byte and FLOP counts are `work_unimp.py`'s.

`labelin_ms`, `dotattn_ms` and `qkv_ms` are PARTS of `encode_ms` (the
encoder module's whole self time), not beside it: what `encode_ms`
holds besides them is what the compiler rooted in none of them (the
rows' re-ordering; a hop's dequantise where it fuses elsewhere). Every
reader returns None where the trace holds nothing of what it reads: no
device plane, or a program without these scopes (any commit before this
encoder, any other configuration).
"""

from __future__ import annotations

import functools
import re

from . import reduce_trace, scope_readers
from .readers import _work

# charged to the first that matches; all only under the encoder module
PARTS = (
    ("labelin", re.compile(r"\bencoder/(.*/)?labelin/hop\d+\b")),
    ("dotattn", re.compile(r"\bencoder/.*/layer\d+/attn\b")),
    ("qkv", re.compile(r"\bencoder/.*/layer\d+/(qkv|gate)\b")),
)
OTHER = "other"


@functools.lru_cache(maxsize=None)
def part_of(op_name: str) -> str:
    for part, pattern in PARTS:
        if pattern.search(op_name):
            return part
    return OTHER


def part_seconds(planes: dict):
    """{"labelin": s, "dotattn": s, "qkv": s} of self time, a device,
    over the whole trace; None without a device plane or where no
    operation carries a label-input scope."""
    if not planes["device"]:
        return None
    total = dict.fromkeys([p for p, _ in PARTS], 0.0)
    for ops in planes["device"].values():
        for part, secs in reduce_trace.self_times(
                [(part_of(name), s, d) for name, s, d in ops]).items():
            if part != OTHER:
                total[part] += secs
    if not total["labelin"]:
        return None
    return {p: secs / len(planes["device"]) for p, secs in total.items()}


def _part_ms(ctx, part: str):
    """Self time of the part's operations for one traced step, in ms."""
    planes = scope_readers._planes(ctx)
    win = scope_readers._window(planes) if planes else None
    secs = part_seconds(planes) if win else None
    if not secs:
        return None
    return 1e3 * secs[part] / (win[2] * ctx["window"]["spl"])


def _share_pct(ctx, part: str, counted: str, peak: str):
    """What work_unimp counts under `counted` for one step, over the
    part's time, as a share of the peak; None where either is missing."""
    ms = _part_ms(ctx, part)
    work = _work(ctx) if ms and ctx["peaks"] else {}
    if counted not in work:
        return None
    return 100.0 * work[counted] / (ms / 1e3) / ctx["peaks"][peak]


def labelin_ms(ctx):
    return _part_ms(ctx, "labelin")


def dotattn_ms(ctx):
    return _part_ms(ctx, "dotattn")


def qkv_ms(ctx):
    return _part_ms(ctx, "qkv")


def labelin_hbm_pct(ctx):
    """The label rows of hops 1..L as stored (work_unimp:
    `labelin_bytes`) over the label input's time, as a share of the HBM
    peak. Far under 100 by nature: a row gather is bound by the number
    of rows."""
    return _share_pct(ctx, "labelin", "labelin_bytes", "hbm_bytes_per_s")


def dotattn_hbm_pct(ctx):
    """The bytes the attention must move a step (work_unimp:
    `dotattn_bytes`) over its time, as a share of the HBM peak."""
    return _share_pct(ctx, "dotattn", "dotattn_bytes", "hbm_bytes_per_s")


def qkv_mfu_pct(ctx):
    """The four projections' FLOPs a step (work_unimp: `qkv_flops`) over
    the time of the projections, the gate and the norm, as a share of
    the bf16 peak."""
    return _share_pct(ctx, "qkv", "qkv_flops", "bf16_flops_per_s")
