"""Per-layer metrics of the MAG240M baseline GAT, read from the scopes
its layers, norms and head carry in the one trace
(`euler_tpu/utils/encoders.GATLayer`: `.../encoder/enc/layer<l>/proj`,
`/attn`, `/skip`, `/norm`; `_MLPHead`: `.../encoder/enc/head`, its norm
`head/norm`). The trace is loaded and its compiler-made operations named
by `scope_readers`; the byte and FLOP counts are `work_mag.py`'s.

`wideattn_ms`, `wideproj_ms` and `norm_ms` are PARTS of `encode_ms` (the
encoder module's whole self time), not beside it: what `encode_ms` holds
besides them is what the compiler rooted in none of them (the rows'
re-ordering; a hop's dequantise where it fuses elsewhere).
**`wideattn_ms` IS `attn_ms` and `wideproj_ms` stands where `proj_ms`
stands** (with the head's products): second names, because a metric
reaches a cell only through the `workloads` list of its accepted entry
(PERF.md section 3). Every reader returns None where the trace holds
nothing of what it reads: no device plane, or a program without a norm
scope (any commit before this encoder, any other configuration).
"""

from __future__ import annotations

import functools
import re

from . import reduce_trace, scope_readers
from .readers import _work

# charged to the first that matches; all only under the encoder module
PARTS = (
    ("norm", re.compile(r"\bencoder/.*/norm\b")),
    ("wideattn", re.compile(r"\bencoder/.*/layer\d+/attn\b")),
    ("wideproj", re.compile(r"\bencoder/.*/(layer\d+/(proj|skip)|head)\b")),
)
OTHER = "other"


@functools.lru_cache(maxsize=None)
def part_of(op_name: str) -> str:
    for part, pattern in PARTS:
        if pattern.search(op_name):
            return part
    return OTHER


def part_seconds(planes: dict):
    """{"norm": s, "wideattn": s, "wideproj": s} of self time, a device,
    over the whole trace; None without a device plane or where no
    operation carries a norm scope."""
    if not planes["device"]:
        return None
    total = dict.fromkeys([p for p, _ in PARTS], 0.0)
    for ops in planes["device"].values():
        for part, secs in reduce_trace.self_times(
                [(part_of(name), s, d) for name, s, d in ops]).items():
            if part != OTHER:
                total[part] += secs
    if not total["norm"]:
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
    """What work_mag counts under `counted` for one step, over the
    part's time, as a share of the peak; None where either is missing."""
    ms = _part_ms(ctx, part)
    work = _work(ctx) if ms and ctx["peaks"] else {}
    if counted not in work:
        return None
    return 100.0 * work[counted] / (ms / 1e3) / ctx["peaks"][peak]


def wideattn_ms(ctx):
    return _part_ms(ctx, "wideattn")


def wideproj_ms(ctx):
    return _part_ms(ctx, "wideproj")


def norm_ms(ctx):
    return _part_ms(ctx, "norm")


def wideattn_hbm_pct(ctx):
    """The bytes the attention must move a step (work_mag: `attn_bytes`)
    over its time, as a share of the HBM peak."""
    return _share_pct(ctx, "wideattn", "attn_bytes", "hbm_bytes_per_s")


def wideproj_mfu_pct(ctx):
    """The projections', skips' and head's FLOPs a step (work_mag:
    `proj_flops`) over their time, as a share of the bf16 peak."""
    return _share_pct(ctx, "wideproj", "proj_flops", "bf16_flops_per_s")
