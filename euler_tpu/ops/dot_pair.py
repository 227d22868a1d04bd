"""The attention of one (target hop, source hop) pair of a UniMP layer
(`utils/encoders.TransformerConvLayer`) as one Pallas TPU kernel a pass
(the layer takes it where the backend is the TPU and `row_block` finds
a block: the projected width is a multiple of 128 and a block of
targets fits VMEM).

The pair, a head h at a time, over a target's k sampled slots j, with
no term of its own:

    s_j = (q . k_s[j])_h / sqrt(C),  alpha = softmax over j, pads left out,
    msg = sum_j alpha_j v_s[j]          (the heads concatenated, [M, H*C])

A target whose slots are all pads aggregates zero, with nothing NaN in
either pass (`encoders._slot_softmax`'s `_NO_SELF` own logit).

LAYOUT. Every quantity of a target is laid along its row's H*C lanes:
a head's score, weight and their cotangents stand in each of that
head's C lanes. One product with the heads' block-diagonal 0/1 matrix B
[H*C, H*C] sums a head's lanes into every one of them, so the softmax
and its backward are elementwise on [Mb, H*C] tiles and a weight is
already spread over its head's lanes: no transposes but the mask's.

FORWARD. One grid step a block of Mb targets: q [Mb, H*C], the block's
keys and values [k, Mb, H*C] and its mask are read ONCE, the message
written once. The scores and weights stay in VMEM scratch.

BACKWARD. The same blocks: q, keys, values, mask and the message's
cotangent g read once; the weights are recomputed from q and the keys
(read anyway, for dq), so the forward saves nothing but its inputs.
Then

    dalpha_j = (g * v_s[j]) B,   de_j = alpha_j (dalpha_j - sum_i alpha_i dalpha_i),
    dv_s[j] = alpha_j g,   dk_s[j] = de_j / sqrt(C) * q,   dq = sum_j de_j / sqrt(C) * k_s[j]

with dk_s and dv_s written once, every head in its own lanes.

PRECISION. Rounded where the XLA path (`TransformerConvLayer`'s two
products with the heads' 0/1 indicator at XLA's default precision, one
bfloat16 pass on the TPU) rounds, and nowhere else: q * k_s before the
head sum, the weight a value lane takes, and in the backward g * v_s and
de / sqrt(C), each to bfloat16; the products with B sum in float32, and
the softmax, the weighted sum and every other product and sum are
float32.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from euler_tpu.utils import encoders

Array = jax.Array

LANES = 128
# targets of one grid step: the largest that divides the targets and
# fits the VMEM budget
ROW_BLOCKS = (512, 256, 128)
# the scoped VMEM either call may take (a v5e core has 128 MiB): a
# block's keys and values and, backward, their cotangents, each in the
# pipeline's two buffers, are its bulk. By `vmem_bytes` cell 5's
# [10, M, 128] pairs take 29.9 MiB at 512 targets a block, over
# Mosaic's default 16
VMEM_LIMIT = 64 << 20
VMEM_BUDGET = VMEM_LIMIT * 7 // 8


def vmem_bytes(rows: int, slots: int, lanes: int) -> int:
    """What a backward grid step of `rows` targets holds in VMEM (the
    forward holds less): every block of an operand or a result twice
    (the pipeline's two buffers), the weights' and their cotangents'
    scratch once, the step's own [rows, lanes] tiles, and B."""
    sub = -(-slots // 8) * 8          # the mask's slots on sublanes
    per_row = 4 * (2 * 2 * slots * lanes      # keys and values
                   + 2 * 2 * slots * lanes    # their cotangents
                   + 2 * 3 * lanes            # q, g and dq
                   + 2 * sub                  # the mask
                   + 2 * slots * lanes        # weights, their cotangents
                   + 8 * lanes)               # tiles
    return rows * per_row + 2 * 2 * lanes * lanes     # B, bfloat16


def row_block(slots: int, rows: int, lanes: int,
              heads: int) -> Optional[int]:
    """The targets of one grid step for a pair of `rows` targets with
    `slots` sampled slots of `lanes` = H * C projected lanes, or None
    where the shapes do not tile or no block fits: H*C a multiple of 128
    and of the heads, the targets a multiple of a row block whose
    `vmem_bytes` is within VMEM_BUDGET."""
    if lanes % LANES or lanes % heads:
        return None
    return next((b for b in ROW_BLOCKS if rows % b == 0 and vmem_bytes(
        b, slots, lanes) <= VMEM_BUDGET), None)


def _bf16(v):
    """v at bfloat16's precision, kept in float32."""
    return v.astype(jnp.bfloat16).astype(jnp.float32)


def _head_sums(v, b_ref):
    """[Mb, H*C] -> each head's sum of v over its C lanes, in every one
    of them: v rounded to bfloat16, one pass, float32 sums."""
    return jax.lax.dot_general(v.astype(jnp.bfloat16), b_ref[...],
                               (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _live(mask_ref, j, lanes):
    """Slot j's mask of the block's targets, [1, Mb] along the lanes ->
    [Mb, lanes] True where the slot is drawn."""
    row = mask_ref[j:j + 1, :]
    tile = jnp.transpose(jnp.broadcast_to(row, (LANES, row.shape[1])))
    return jnp.concatenate([tile] * (lanes // LANES), axis=1) > 0


def _weights(q, ks_ref, mask_ref, b_ref, a_sc, scale):
    """The block's softmax weights over its slots into a_sc [k, Mb, H*C]
    (`encoders._slot_softmax` with the `_NO_SELF` own logit: the same
    operations in the same order)."""
    slots, _, lanes = ks_ref.shape
    top = jnp.full(q.shape, encoders._NO_SELF, jnp.float32)
    for j in range(slots):
        e = _head_sums(q * ks_ref[j], b_ref) / scale
        if mask_ref is not None:
            e = jnp.where(_live(mask_ref, j, lanes), e, -jnp.inf)
        a_sc[j] = e
        top = jnp.maximum(top, e)
    total = jnp.zeros_like(q)
    for j in range(slots):
        p = jnp.exp(a_sc[j] - top)
        a_sc[j] = p
        total = total + p
    total = jnp.exp(encoders._NO_SELF - top) + total
    for j in range(slots):
        a_sc[j] = a_sc[j] / total


def _fwd_kernel(q_ref, ks_ref, vs_ref, b_ref, *rest, scale, masked):
    mask_ref, (msg_ref, a_sc) = (rest[0], rest[1:]) if masked else (
        None, rest)
    _weights(q_ref[...], ks_ref, mask_ref, b_ref, a_sc, scale)
    msg = jnp.zeros(msg_ref.shape, jnp.float32)
    for j in range(ks_ref.shape[0]):
        msg = msg + _bf16(a_sc[j]) * vs_ref[j]
    msg_ref[...] = msg


def _bwd_kernel(q_ref, ks_ref, vs_ref, b_ref, *rest, scale, masked):
    mask_ref, (g_ref, dq_ref, dks_ref, dvs_ref, a_sc, da_sc) = (
        rest[0], rest[1:]) if masked else (None, rest)
    q, g = q_ref[...], g_ref[...]
    _weights(q, ks_ref, mask_ref, b_ref, a_sc, scale)
    slots = ks_ref.shape[0]
    mean_da = jnp.zeros_like(q)          # sum_i alpha_i dalpha_i
    for j in range(slots):
        a = a_sc[j]
        da = _head_sums(g * vs_ref[j], b_ref)
        da_sc[j] = da
        dvs_ref[j] = _bf16(a) * g
        mean_da = mean_da + a * da
    dq = jnp.zeros_like(q)
    for j in range(slots):
        de = _bf16(a_sc[j] * (da_sc[j] - mean_da) / scale)
        dks_ref[j] = de * q
        dq = dq + de * ks_ref[j]
    dq_ref[...] = dq


# jitted so that a shape's kernel is traced once a process: Pallas traces
# the kernel anew at every call, and each program of the step calls it
# (cell 5: 5 pairs, each forward traced twice under autodiff; set-up
# took 7-8 s longer on the chip without the jit)
@functools.partial(jax.jit, static_argnums=(0, 1, 6))
def _call(kernel, name, q, k_s, v_s, mask, heads, g=None):
    """One pallas_call over the blocks of targets: the forward's message
    [M, H*C], or with the cotangent g the backward's dq [M, H*C], dk_s
    and dv_s [k, M, H*C], all float32."""
    slots, rows, lanes = k_s.shape
    tb = row_block(slots, rows, lanes, heads)
    target = pl.BlockSpec((tb, lanes), lambda i: (i, 0))
    slot_rows = pl.BlockSpec((slots, tb, lanes), lambda i: (0, i, 0))
    b = jnp.kron(jnp.eye(heads, dtype=jnp.bfloat16),
                 jnp.ones((lanes // heads,) * 2, jnp.bfloat16))
    ins = [q, k_s, v_s, b]
    specs = [target, slot_rows, slot_rows,
             pl.BlockSpec((lanes, lanes), lambda i: (0, 0))]
    if mask is not None:
        ins.append(mask.astype(jnp.float32))
        specs.append(pl.BlockSpec((slots, tb), lambda i: (0, i)))
    outs = [jax.ShapeDtypeStruct(q.shape, jnp.float32)]
    out_specs = [target]
    if g is not None:
        ins.append(g)
        specs.append(target)
        outs += [jax.ShapeDtypeStruct(k_s.shape, jnp.float32)] * 2
        out_specs += [slot_rows] * 2
    return pl.pallas_call(
        functools.partial(kernel, scale=math.sqrt(lanes // heads),
                          masked=mask is not None),
        grid=(rows // tb,),
        in_specs=specs,
        out_specs=out_specs,
        out_shape=outs,
        scratch_shapes=[pltpu.VMEM((slots, tb, lanes), jnp.float32)] * (
            1 if g is None else 2),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",), vmem_limit_bytes=VMEM_LIMIT),
        name=name,
    )(*ins)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def attend(q: Array, k_s: Array, v_s: Array, mask: Optional[Array],
           heads: int) -> Array:
    """The pair's attention (module docstring): q [M, H*C] the targets'
    queries, k_s and v_s [k, M, H*C] their slots' keys and values, mask
    bool [k, M] (False at a pad slot) or None -> [M, H*C], the heads
    concatenated. Both passes are the Pallas kernels (`row_block` must
    find a block); off the TPU it runs only under
    `pltpu.force_tpu_interpret_mode()` (the tests')."""
    msg, = _call(_fwd_kernel, "dot_pair_fwd", q, k_s, v_s, mask, heads)
    return msg.astype(q.dtype)


def _attend_fwd(q, k_s, v_s, mask, heads):
    return attend(q, k_s, v_s, mask, heads), (q, k_s, v_s, mask)


def _attend_bwd(heads, res, g):
    q, k_s, v_s, mask = res
    dq, dk_s, dv_s = _call(_bwd_kernel, "dot_pair_bwd", q, k_s, v_s, mask,
                           heads, g.astype(jnp.float32))
    return (dq.astype(q.dtype), dk_s.astype(k_s.dtype),
            dv_s.astype(v_s.dtype), None)


attend.defvjp(_attend_fwd, _attend_bwd)
