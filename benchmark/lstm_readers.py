"""Per-layer metrics of the GraphSAGE-LSTM configuration, read from the
scope its recurrences carry in the one trace
(`euler_tpu/utils/aggregators.LSTMAggregator`: `.../encoder/enc/
agg_<l>/lstm`). On the TPU, where the shapes tile, a recurrence there is
two Pallas calls, `lstm_fwd` and `lstm_bwd` (`ops/slot_lstm.py`), with
the mask's re-layout beside them; elsewhere it is the `lax.scan` over
the slots (the input and recurrent products, the gates, the saved
carries, forward and backward, the loop itself). Either way the scope
holds it whole. The trace is loaded and its compiler-made operations
named by `scope_readers`; the FLOP and byte counts are `work_lstm.py`'s.

`lstm_ms` is a PART of `encode_ms` (the encoder module's whole self
time), not beside it: what `encode_ms` holds besides it is the S and N
products, the activations, the l2 norm and what the compiler rooted in
no recurrence (the ids' re-order). Every reader returns None where the
trace holds nothing of what it reads: no device plane, or a program
without an `lstm` scope (any commit before this aggregator, any other
configuration).
"""

from __future__ import annotations

import functools
import re

from . import reduce_trace, scope_readers
from .readers import _work

LSTM = re.compile(r"\bencoder/.*/lstm\b")


@functools.lru_cache(maxsize=None)
def in_lstm(op_name: str) -> bool:
    return bool(LSTM.search(op_name))


def lstm_seconds(planes: dict):
    """Self time under an `lstm` scope, a device, over the whole trace;
    None without a device plane or where no operation carries it."""
    if not planes["device"]:
        return None
    total = 0.0
    for ops in planes["device"].values():
        total += reduce_trace.self_times(
            [(in_lstm(name), s, d) for name, s, d in ops]).get(True, 0.0)
    return total / len(planes["device"]) if total else None


def lstm_ms(ctx):
    """Self time of the recurrences for one traced step, in ms."""
    planes = scope_readers._planes(ctx)
    win = scope_readers._window(planes) if planes else None
    secs = lstm_seconds(planes) if win else None
    if not secs:
        return None
    return 1e3 * secs / (win[2] * ctx["window"]["spl"])


def lstm_roofline(ctx):
    """The least time the recurrences could take a step, the larger of
    work_lstm's `lstm_flops` over the bf16 peak and its `lstm_bytes` over
    the HBM peak, as a share of `lstm_ms`; None where either is missing."""
    ms = lstm_ms(ctx)
    work = _work(ctx) if ms and ctx["peaks"] else {}
    if "lstm_flops" not in work:
        return None
    floor = max(work["lstm_flops"] / ctx["peaks"]["bf16_flops_per_s"],
                work["lstm_bytes"] / ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * floor / (ms / 1e3)
