"""Per-layer metrics read from the program's own names in the one trace:
its spans on the host plane (euler_tpu.obs spans are `euler.<name>`
events there while the profiler runs) and its jax.named_scope names on
the device plane's operations. SCOPES.md says what was found in a trace
by hand and how each number is made.

`load` turns the .xplane.pb into plain lists once a trace directory;
`idle_shares` and `scope_seconds` work on those lists, so a test can hand
them planes it made up. Every reader returns None where the trace holds
nothing of what it reads (no device plane; a program without the spans or
the scopes), never 0.
"""

from __future__ import annotations

import functools
import re

from . import kernel_work, reduce_trace, xplane_metadata

SPAN_PREFIX = "euler."
# the stat of an "XLA Ops" event's METADATA that holds the operation's name
# with the scopes it was traced under, e.g.
# jit(train_loop)/while/body/closed_call/jvp(Model)/Model.embed/draw/hop1/gather:
OP_NAME_STAT = "tf_op"

# a device operation is charged to the first of these its name holds, so
# one under two names (the encoder module holds the feature gather and the
# activation cache) is charged once
SCOPES = (
    ("draw", re.compile(r"\bdraw/")),
    ("gather", re.compile(r"\bgather/")),
    ("cache", re.compile(r"\bcache(_\d+)?\b")),
    ("update", re.compile(r"\b(update|guard)\b")),
    ("encode", re.compile(r"\bencoder\b")),
)
UNSCOPED = "unscoped"
# an op name that ends at a frame of the step's own nesting names nothing:
# the compiler made the operation (a hoisted broadcast, an inserted copy)
NAMELESS = re.compile(r"^(jit\([^)]*\))?(/(while|body|cond|closed_call))*:?$")
OPERAND = re.compile(r"%[\w.\-]+")
# the train thread's phases the idle time is laid under, by metric
IDLE_PHASES = {
    "input_wait": ("input_wait",),
    "enqueue": ("stack", "device_step"),
    "result_wait": ("result_wait",),
}


@functools.lru_cache(maxsize=None)   # a program has a few hundred op names
def scope_of(op_name: str) -> str:
    for scope, pattern in SCOPES:
        if pattern.search(op_name):
            return scope
    return UNSCOPED


def inherit_names(op_names: dict) -> dict:
    """{HLO text of an operation: its op name}, with each operation whose
    own op name names nothing (NAMELESS) given the op name of the nearest
    named operation that reads its result, found through the operands in
    the HLO texts. One that only unrecorded operations read (a tuple, a
    conditional's operand) stays as it is."""
    text_of = {text.partition(" = ")[0]: text for text in op_names}
    readers = {}
    for text in op_names:
        for operand in set(OPERAND.findall(text.partition(" = ")[2])):
            if operand in text_of:
                readers.setdefault(text_of[operand], []).append(text)
    out = {}
    for text, own in op_names.items():
        seen, front = {text}, [text]
        while front and NAMELESS.match(own):
            front = [r for t in front for r in readers.get(t, ())
                     if r not in seen]
            seen.update(front)
            own = next((op_names[r] for r in front
                        if not NAMELESS.match(op_names[r])), own)
        out[text] = own
    return out


@functools.lru_cache(maxsize=1)
def load(trace_dir: str) -> dict:
    """{"device": {plane: [(op name with scopes, start_ns, dur_ns)]},
    "host": [[(event name, start_ns, dur_ns)] per thread]}; of the host's
    events only the program's spans and the runner's dispatch span."""
    from jax.profiler import ProfileData

    out = {"device": {}, "host": []}
    path = reduce_trace.find_xplane(trace_dir)
    metadata = xplane_metadata.event_metadata_stats(path)
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            if reduce_trace._is_device(plane.name):
                if line.name not in reduce_trace.OP_LINES:
                    continue
                events = [(ev.name, float(ev.start_ns),
                           float(ev.duration_ns)) for ev in line.events]
                stats = metadata.get(plane.name, {})
                names = inherit_names({
                    text: stats.get(text, {}).get(OP_NAME_STAT) or ""
                    for text in {text for text, _, _ in events}})
                out["device"].setdefault(plane.name, []).extend(
                    (names[text] or text, s, d) for text, s, d in events)
            else:
                kept = [(ev.name, float(ev.start_ns), float(ev.duration_ns))
                        for ev in line.events
                        if ev.name.startswith(SPAN_PREFIX)
                        or ev.name == reduce_trace.DISPATCH_SPAN]
                if kept:
                    out["host"].append(kept)
    return out


def _window(planes: dict):
    """(start_ns, end_ns, dispatches) of the runner's traced dispatches."""
    spans = sorted((s, s + d) for line in planes["host"]
                   for name, s, d in line
                   if name == reduce_trace.DISPATCH_SPAN)
    if not spans:
        return None
    return spans[0][0], spans[-1][1], len(spans)


def overlap_seconds(a, b) -> float:
    """Length of what the union of intervals `a` shares with that of `b`."""
    union = reduce_trace.union_seconds
    return union(a) + union(b) - union(list(a) + list(b))


def idle_shares(planes: dict):
    """The device's idle time in the traced window, as shares of the
    window in %, by what the train thread was in meanwhile: {"input_wait",
    "enqueue", "result_wait", "unattributed", "idle"}; the first four add
    up to the last, which is reduce_trace's idle share. The train thread
    is the one that holds `euler.train_dispatch`; None without it, without
    a device plane or without the runner's dispatch spans."""
    win = _window(planes)
    train = [line for line in planes["host"] if any(
        name == SPAN_PREFIX + "train_dispatch" for name, _, _ in line)]
    if win is None or not train or not planes["device"]:
        return None
    lo, hi, _ = win
    phases = {key: [(s, s + d) for line in train for name, s, d in line
                    if name in {SPAN_PREFIX + n for n in names}]
              for key, names in IDLE_PHASES.items()}
    shares = dict.fromkeys((*IDLE_PHASES, "unattributed", "idle"), 0.0)
    for ops in planes["device"].values():
        busy = [(s, s + d) for _, s, d in ops]
        idle_s = (hi - lo) / 1e9 - reduce_trace.union_seconds(busy)
        gaps = reduce_trace.gaps(busy, lo, hi)
        shares["idle"] += idle_s
        shares["unattributed"] += idle_s
        for key, spans in phases.items():
            under = overlap_seconds(gaps, spans)
            shares[key] += under
            shares["unattributed"] -= under
    scale = 100.0 / (len(planes["device"]) * (hi - lo) / 1e9)
    return {key: v * scale for key, v in shares.items()}


def scope_seconds(planes: dict):
    """{scope: seconds of self time, a device} over the whole trace, with
    "unscoped" for the operations under none of SCOPES; None without a
    device plane, and where no operation carries a draw or a gather scope
    (a program that does not name its kernels)."""
    if not planes["device"]:
        return None
    total = dict.fromkeys([s for s, _ in SCOPES] + [UNSCOPED], 0.0)
    for ops in planes["device"].values():
        for scope, secs in reduce_trace.self_times(
                [(scope_of(name), s, d) for name, s, d in ops]).items():
            total[scope] += secs
    if not total["draw"] and not total["gather"]:
        return None
    return {scope: secs / len(planes["device"])
            for scope, secs in total.items()}


# -- the readers ---------------------------------------------------------------
def _planes(ctx):
    trace_dir = ctx["window"].get("trace")
    return load(trace_dir) if trace_dir else None


def _idle(ctx, key: str):
    planes = _planes(ctx)
    shares = idle_shares(planes) if planes else None
    return shares[key] if shares else None


def idle_input_wait_pct(ctx):
    return _idle(ctx, "input_wait")


def idle_enqueue_pct(ctx):
    return _idle(ctx, "enqueue")


def idle_result_wait_pct(ctx):
    return _idle(ctx, "result_wait")


def idle_unattributed_pct(ctx):
    return _idle(ctx, "unattributed")


def feeder_produce_ms(ctx):
    """Mean length of the feeder's produce spans that start inside the
    traced window."""
    planes = _planes(ctx)
    win = _window(planes) if planes else None
    if win is None:
        return None
    lo, hi, _ = win
    took = [d for line in planes["host"] for name, s, d in line
            if name == SPAN_PREFIX + "feeder_produce" and lo <= s < hi]
    return sum(took) / len(took) / 1e6 if took else None


def _scope_ms(ctx, scope: str):
    """Self time of the scope's operations for one traced step, in ms."""
    planes = _planes(ctx)
    win = _window(planes) if planes else None
    secs = scope_seconds(planes) if win else None
    if not secs:
        return None
    return 1e3 * secs[scope] / (win[2] * ctx["window"]["spl"])


def draw_ms(ctx):
    return _scope_ms(ctx, "draw")


def gather_ms(ctx):
    return _scope_ms(ctx, "gather")


def encode_ms(ctx):
    return _scope_ms(ctx, "encode")


def update_ms(ctx):
    return _scope_ms(ctx, "update")


def cache_ms(ctx):
    return _scope_ms(ctx, "cache")


def unscoped_pct(ctx):
    planes = _planes(ctx)
    secs = scope_seconds(planes) if planes else None
    if not secs:
        return None
    return 100.0 * secs[UNSCOPED] / sum(secs.values())


def _hbm_pct(ctx, kernel: str):
    """The kernel's rows a step x a row's stored bytes over its time, as
    a share of the HBM peak. Far under 100 by nature: these gathers are
    bound by the number of rows, not by bytes."""
    ms = _scope_ms(ctx, kernel)
    if not ms or not ctx["peaks"]:
        return None
    weighted = ctx["traffic"]["edge_weights"]["kind"] != "unit"
    rows, row_bytes = kernel_work.for_config(ctx["cfg"])(
        ctx["cfg"], int(ctx["traffic"]["root_batch"]), weighted)[kernel]
    return 100.0 * rows * row_bytes / (ms / 1e3) \
        / ctx["peaks"]["hbm_bytes_per_s"]


def gather_hbm_pct(ctx):
    return _hbm_pct(ctx, "gather")


def draw_hbm_pct(ctx):
    return _hbm_pct(ctx, "draw")
