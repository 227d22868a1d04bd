"""Per-layer metrics of the graph-attention encoder, read from the scopes
its layers carry in the one trace (`euler_tpu/utils/encoders.GATLayer`:
`.../encoder/enc/layer<l>/proj`, `/attn`, `/skip`). The trace is loaded
and its compiler-made operations named by `scope_readers`; the byte and
FLOP counts are `work_gat.py`'s.

`attn_ms` and `proj_ms` are PARTS of `encode_ms` (the encoder module's
whole self time), not beside it: what `encode_ms` holds besides them is
what the compiler rooted in neither (the hop rows' dequantise where it
fuses into a consumer, the rows' re-ordering). Every reader returns None
where the trace holds nothing of what it reads: no device plane, or a
program without these scopes (any commit before this encoder).
"""

from __future__ import annotations

import functools
import re

from . import reduce_trace, scope_readers
from .readers import _work

# charged to the first that matches; both only under the encoder module
PARTS = (
    ("attn", re.compile(r"\bencoder/.*/attn\b")),
    ("proj", re.compile(r"\bencoder/.*/(proj|skip)\b")),
)
OTHER = "other"


@functools.lru_cache(maxsize=None)
def part_of(op_name: str) -> str:
    for part, pattern in PARTS:
        if pattern.search(op_name):
            return part
    return OTHER


def part_seconds(planes: dict):
    """{"attn": s, "proj": s} of self time, a device, over the whole
    trace; None without a device plane or where no operation carries an
    attention scope."""
    if not planes["device"]:
        return None
    total = dict.fromkeys([p for p, _ in PARTS], 0.0)
    for ops in planes["device"].values():
        for part, secs in reduce_trace.self_times(
                [(part_of(name), s, d) for name, s, d in ops]).items():
            if part != OTHER:
                total[part] += secs
    if not total["attn"]:
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


def attn_ms(ctx):
    return _part_ms(ctx, "attn")


def proj_ms(ctx):
    return _part_ms(ctx, "proj")


def _share_pct(ctx, part: str, counted: str, peak: str):
    """What work_gat counts under `counted` for one step, over the part's
    time, as a share of the peak; None where either is missing."""
    ms = _part_ms(ctx, part)
    work = _work(ctx) if ms and ctx["peaks"] else {}
    if counted not in work:
        return None
    return 100.0 * work[counted] / (ms / 1e3) / ctx["peaks"][peak]


def attn_hbm_pct(ctx):
    """The bytes the attention must move a step (work_gat: `attn_bytes`)
    over its time, as a share of the HBM peak."""
    return _share_pct(ctx, "attn", "attn_bytes", "hbm_bytes_per_s")


def proj_mfu_pct(ctx):
    """The projections' and skips' FLOPs a step (work_gat: `proj_flops`)
    over their time, as a share of the bf16 peak."""
    return _share_pct(ctx, "proj", "proj_flops", "bf16_flops_per_s")
