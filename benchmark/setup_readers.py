"""Per-layer metrics of SET-UP, read from the program's own histograms
and counters (SETUP_SPANS.md). Each reads `ctx["window"]["obs_before"]`,
the registry as `cell.Program.window` snapshots it when the window
starts: whatever it holds by then was spent before the window, so it is
set-up's. Every reader returns None where the program lacks the family
(a commit before PR 34), never 0.
"""

from __future__ import annotations


def _children(ctx, family: str):
    """{label string: value} of a metric family at the window's start,
    None where the program has no such family."""
    metric = ctx["window"]["obs_before"].get(family)
    return None if metric is None else metric["values"]


def _labels(key: str) -> dict:
    """"table=nbr,stage=transfer" -> {"table": "nbr", "stage": "transfer"}."""
    return dict(part.split("=", 1) for part in key.split(",") if part)


def _seconds(children, keep=lambda labels: True):
    """Sum of the histogram children's millisecond sums, in seconds."""
    return sum(float(h["sum"]) for key, h in children.items()
               if keep(_labels(key))) / 1e3


def setup_place_s(ctx):
    """The constructors' parent spans, `place_features` and
    `place_neighbors`: placement_ms under the parents' own stage names."""
    children = _children(ctx, "placement_ms")
    if children is None:
        return None
    return _seconds(children,
                    lambda labels: labels["stage"].startswith("place_"))


def setup_train_s(ctx):
    """Every est.train call before the window, whole: the state-init
    call, the single steps and the first scanned dispatch."""
    children = _children(ctx, "estimator_train_call_ms")
    return None if children is None else _seconds(children)


def setup_compile_s(ctx):
    """Self time of trace, lower, compile and cache_fetch over every
    function: a part of setup_train_s and, where a put jits, of
    setup_place_s and of the harness's own time."""
    children = _children(ctx, "estimator_compile_ms")
    return None if children is None else _seconds(children)


def setup_cache_misses(ctx):
    """Executables set-up had to compile: 0 in a warm run."""
    if _children(ctx, "estimator_compile_ms") is None:
        return None
    # a warm run has the histogram and no `miss` child: that is a 0
    children = _children(ctx, "estimator_compiles_total") or {}
    return float(sum(v for key, v in children.items()
                     if _labels(key)["cache"] == "miss"))
