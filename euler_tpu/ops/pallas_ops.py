"""Pallas TPU kernels for the hot host-feeder ops.

The fanout training path gathers each output row's k neighbor feature
rows from the HBM-resident table and mean-reduces them:
    out[i] = mean_j table[rows[i, j]]      # rows: [n, k] int32
XLA expresses this as gather → reshape → mean, materializing the
[n·k, D] intermediate in HBM (written then re-read: 2·n·k·D·4 bytes of
traffic). The fused kernel streams each neighbor row HBM→VMEM once and
accumulates in VMEM, cutting HBM traffic to n·k·D·4 + n·D·4.

CLOSED NEGATIVE RESULT (round 5 — PERF.md "Pallas gather: closed").
gather_mean() defaults to the XLA formulation and that is the final
verdict, not an interim one:
- Small-scale (200k x 128 f32 table): the fused kernel was within 2x of
  XLA's gather in either direction, no reproducible win.
- Per-row DMA cost analysis (round 4): at d=100 bf16 a row is ~200B,
  so each async copy moves less than one 512B HBM burst and the
  issue/semaphore overhead dominates — the per-row design loses
  regardless of tile_n.
- The XLA-side A/Bs (pad128 59.6ms vs plain 59.8ms vs
  promise_in_bounds 58.6ms on the 4.9M-row hop-2 gather) show the
  gather is HBM-random-access-bound, not layout-bound.
The hop-2 gather was instead removed structurally (the in-jit
historical-activation cache, parallel/encoders — 4.2x step-time win).

WHAT MOSAIC ACCEPTS (local compile for a described v5e:2x2, PR 21 —
tests/test_chip_compile.py keeps the accepting case):
- float32 table, D a multiple of 128 (e.g. [2450001, 128] f32 with rows
  [491520, 10]): compiles, both semaphore layouts.
- bf16 / int8 tables: refused — "Slice shape along dimension 0 must be
  aligned to tiling (8), but is 1". Sub-32-bit rows pack several per
  sublane, so the one-row `pl.ds(row, 1)` DMA below is not a legal
  slice of the tiled HBM layout. These are the widths the trainer
  stores (int8/bf16 × 100), so the kernel does not cover the main path.
- D not a multiple of 128 (e.g. 100): refused — "Slice shape along
  dimension 1 must be aligned to tiling (128)".
- integer tables: refused at trace time — the float mean cannot be
  stored into an integer out_ref.
_check_kernel_shapes rejects all of these up front with a ValueError
that says why, and gather_mean(use_pallas=True) raises instead of
quietly returning the XLA result. The kernel stays as the validated
template for neighbor-indexed fusions XLA can't express (interpret-mode
tests pin numerics), not as a performance path.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

Array = jax.Array

# default output rows per grid step: amortizes control overhead while
# keeping k·D scratch well under VMEM
_TILE_N = 8


def _xla_gather_mean(table: Array, rows: Array) -> Array:
    n, k = rows.shape
    return jnp.take(table, rows.reshape(-1), axis=0) \
        .reshape(n, k, table.shape[-1]).mean(axis=1)


def _kernel(rows_ref, table_ref, out_ref, scratch, sems, *,
            one_sem: bool):
    """One grid step: gather k rows for each of tile_n outputs, reduce.
    rows_ref is this step's (tile_n, k) index block in SMEM. All
    tile_n·k row fetches are in flight at once (start all, then wait) —
    serializing them makes the kernel DMA-latency-bound.

    one_sem selects the semaphore layout: a per-copy semaphore array
    (sems.at[idx]) vs ONE shared DMA semaphore every copy signals and
    each wait consumes once. Both compile for the v5e at the accepted
    shapes; the profiler A/Bs them over the same body."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    tile_n, k = rows_ref.shape

    def dma_for(idx):
        row = rows_ref[idx // k, idx % k]
        return pltpu.make_async_copy(
            table_ref.at[pl.ds(row, 1), :],
            scratch.at[pl.ds(idx, 1), :],
            sems if one_sem else sems.at[idx],
        )

    def start(idx, _):
        dma_for(idx).start()
        return 0

    def wait(idx, _):
        dma_for(idx).wait()
        return 0

    jax.lax.fori_loop(0, tile_n * k, start, 0)
    jax.lax.fori_loop(0, tile_n * k, wait, 0)
    d = scratch.shape[-1]
    out_ref[:, :] = jnp.mean(scratch[:, :].reshape(tile_n, k, d), axis=1)


def _check_kernel_shapes(table, rows, tile_n: int) -> None:
    """Reject up front what the TPU's Mosaic compiler refuses (module
    docstring), with the reason — not a MosaicError from deep inside a
    jitted step."""
    n, _ = rows.shape
    d = table.shape[-1]
    if n % tile_n != 0:
        raise ValueError(
            f"pallas gather_mean: {n} output rows are not a multiple of "
            f"the row tile tile_n={tile_n}")
    if table.dtype != jnp.float32:
        raise ValueError(
            f"pallas gather_mean: table dtype {table.dtype} is not "
            "float32 — Mosaic refuses the one-row DMA from a sub-32-bit "
            "table (slices must be aligned to the 8-row tiling, a row is "
            "1), and a float mean cannot be stored into an integer "
            "output")
    if d % 128 != 0:
        raise ValueError(
            f"pallas gather_mean: feature dim {d} is not a multiple of "
            "128 — Mosaic refuses a row slice narrower than the 128-lane "
            "tiling")


@functools.partial(jax.jit,
                   static_argnames=("tile_n", "interpret", "one_sem"))
def _pallas_gather_mean(table: Array, rows: Array, tile_n: int = _TILE_N,
                        interpret: bool = False,
                        one_sem: bool = False) -> Array:
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    _check_kernel_shapes(table, rows, tile_n)
    n, k = rows.shape
    d = table.shape[-1]
    return pl.pallas_call(
        functools.partial(_kernel, one_sem=one_sem),
        grid=(n // tile_n,),
        in_specs=[
            # this step's index block rides SMEM (DMA addresses are
            # scalar reads); the table stays wherever it lives (HBM)
            pl.BlockSpec((tile_n, k), lambda i: (i, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((tile_n, d), lambda i: (i, 0),
                               memory_space=pltpu.VMEM),
        scratch_shapes=[
            pltpu.VMEM((tile_n * k, d), table.dtype),
            pltpu.SemaphoreType.DMA if one_sem
            else pltpu.SemaphoreType.DMA((tile_n * k,)),
        ],
        out_shape=jax.ShapeDtypeStruct((n, d), table.dtype),
        interpret=interpret,
    )(rows, table)


def gather_mean(table: Array, rows: Array,
                use_pallas: bool = False, tile_n: int = _TILE_N) -> Array:
    """out[i] = mean over k of table[rows[i]]; rows [n, k] int32.

    Default is the XLA gather+mean (see module docstring for the
    measured tradeoff). use_pallas=True runs the fused Pallas kernel or
    raises: off a TPU with a RuntimeError, and for shapes/dtypes the
    compiler refuses with _check_kernel_shapes' ValueError. It never
    returns the XLA result under the kernel's name.
    """
    if not use_pallas:
        return _xla_gather_mean(table, rows)
    if jax.default_backend() != "tpu":
        raise RuntimeError(
            "gather_mean(use_pallas=True) needs the TPU backend, this "
            f"process runs {jax.default_backend()!r}; pass "
            "use_pallas=False for the XLA formulation")
    return _pallas_gather_mean(table, rows, tile_n=tile_n)
