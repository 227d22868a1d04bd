"""The attention of one (target hop, source hop) pair of a GAT layer
(`utils/encoders.GATLayer`) with its backward pass as one Pallas TPU
kernel (the layer takes it where the backend is the TPU and `row_block`
finds a block: a head's width is a multiple of 128 and a block of
targets fits VMEM).

The pair, a head h at a time, over a target's k sampled slots j and
itself:

    s_j = a_src . z_s[j],  e_j = LeakyReLU_0.2(s_j + t_dst),
    e_self = LeakyReLU_0.2(t_src + t_dst),
    (a_self, a_j) = softmax over (self, j) with pad slots left out,
    y = a_self z_t + sum_j a_j z_s[j]     (heads concatenated, or averaged)

where t_src, t_dst are the target's own scores (a_src . z_t, a_dst . z_t),
handed in; the sources' scores are formed here, so that their cotangent
is summed where z_s is read anyway.

FORWARD. The XLA path's expressions (`encoders._slot_softmax`,
`encoders._weighted_sum`), the same operations at the same precision.
It saves the weights and the logits, [k, H, M] and [H, M] float32, and
no array of z_s's size: z_s and z_t are the pair's own inputs.

BACKWARD. One grid step a block of targets. Under autodiff XLA wrote the
weighted sum's cotangent a head at a time, padded the pieces to the full
width and added them, and read z_s twice more, for the weights' and for
a_src's cotangent. Here a block's slots [k, Mb, H*C] are read ONCE into
VMEM; a first sweep over the slots forms each weight's cotangent
da_j = <g_h, z_s[j]>, the softmax's and the LeakyReLU's backward give
the logits' cotangent de_j, and a second sweep writes

    dz_s[j] = a_j g_h + de_j a_src,    d a_src += sum_rows de_j z_s[j]

every head into its own lanes of one [k, Mb, H*C] block, written ONCE.
The target's rows (dz_t = a_self g_h) and its scores' cotangents
(dt_src = de_self, dt_dst = de_self + sum_j de_j) come with it; a_src's
cotangent is summed a block at a time and the blocks' sums outside.
Quantities of one target (weights, their cotangents) are laid along the
lanes, M minor, and a block's rows are put on the lanes or spread over a
row's lanes by transposing [Mb, 128] tiles.

PRECISION. Every product and sum of the backward is float32, on the
vector unit: no bfloat16 pass.
"""

from __future__ import annotations

import functools
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
# the scoped VMEM the backward may take (a v5e core has 128 MiB): a
# block's k slots and their cotangent, each in the pipeline's two buffers,
# are its bulk. By `vmem_bytes`, MAG240M's [15, 25600, 1024] pair takes
# 67.8 MiB at 256 targets a block and its [25, 1024, 1024] pairs 54.1 at
# 128, over Mosaic's default 16
VMEM_LIMIT = 96 << 20
VMEM_BUDGET = VMEM_LIMIT * 7 // 8
SLOPE = 0.2


def vmem_bytes(rows: int, slots: int, lanes: int, heads: int,
               out: int) -> int:
    """What a grid step of `rows` targets holds in VMEM: every block of
    an operand or a result twice (the pipeline's two buffers), the
    weights' cotangent scratch once, and the step's own [rows, 128]
    tiles and their transposes."""
    sub = -(-heads // 8) * 8          # heads on sublanes, padded to a tile
    per_row = 4 * (2 * 2 * slots * lanes      # z_s and its cotangent
                   + 2 * 2 * lanes            # z_t and its cotangent
                   + 2 * out                  # the output's cotangent
                   + 2 * 2 * slots * sub      # weights and logits
                   + 2 * 4 * sub              # own ones, dt_src, dt_dst
                   + slots * sub              # the weights' cotangent
                   + 8 * LANES)               # tiles and transposes
    return rows * per_row + 4 * 2 * 2 * 8 * lanes     # a_src, its sums


def row_block(slots: int, rows: int, lanes: int, heads: int,
              out: int) -> Optional[int]:
    """The targets of one grid step for a pair of `rows` targets with
    `slots` sampled slots of `lanes` = H * C projected lanes and an
    output of `out` lanes, or None where the shapes do not tile or no
    block fits: C a multiple of 128, the targets a multiple of a row
    block whose `vmem_bytes` is within VMEM_BUDGET."""
    if lanes % heads or (lanes // heads) % LANES:
        return None
    return next((b for b in ROW_BLOCKS if rows % b == 0 and vmem_bytes(
        b, slots, lanes, heads, out) <= VMEM_BUDGET), None)


def _row_sums(tiles):
    """[Mb, 128] tiles -> [1, Mb]: each row's sum over the tiles' lanes,
    laid along the lanes."""
    return jnp.sum(jnp.transpose(sum(tiles)), axis=0, keepdims=True)


def _spread(row):
    """[1, Mb] -> [Mb, 128]: each value in every lane of its row."""
    return jnp.transpose(jnp.broadcast_to(row, (LANES, row.shape[1])))


def _slope(e):
    return jnp.where(e >= 0, 1.0, SLOPE)


def _bwd_kernel(zs_ref, zt_ref, g_ref, a_ref, e_ref, as_ref, es_ref,
                att_ref, dzs_ref, dzt_ref, dsrc_ref, ddst_ref, datt_ref,
                da_sc, *, heads, concat):
    slots, _, lanes = zs_ref.shape
    c = lanes // heads
    tiles = [[slice(h * c + q, h * c + q + LANES)
              for q in range(0, c, LANES)] for h in range(heads)]

    def g_tile(h, t):
        """The cotangent of head h's output, tile t of its lanes."""
        if concat:
            return g_ref[:, tiles[h][t]]
        return g_ref[:, t * LANES:(t + 1) * LANES] * (1.0 / heads)

    def rows(ref, h, j=None):
        return ref[pl.ds(h, 1), :] if j is None else ref[j, pl.ds(h, 1), :]

    # the own term's weight cotangent, and S = sum over (self, j) of a da
    da_self = [_row_sums([g_tile(h, t) * zt_ref[:, lane]
                          for t, lane in enumerate(tiles[h])])
               for h in range(heads)]

    def first(j, s):
        out = []
        for h in range(heads):
            da = _row_sums([g_tile(h, t) * zs_ref[j, :, lane]
                            for t, lane in enumerate(tiles[h])])
            da_sc[j, pl.ds(h, 1), :] = da
            out.append(s[h] + rows(a_ref, h, j) * da)
        return tuple(out)

    total = jax.lax.fori_loop(0, slots, first, tuple(
        rows(as_ref, h) * da_self[h] for h in range(heads)))
    d_self = []
    for h in range(heads):
        a_own = rows(as_ref, h)
        d_self.append(a_own * (da_self[h] - total[h]) * _slope(rows(es_ref,
                                                                    h)))
        dsrc_ref[pl.ds(h, 1), :] = d_self[h]
        spread = _spread(a_own)
        for t, lane in enumerate(tiles[h]):
            dzt_ref[:, lane] = spread * g_tile(h, t)

    def second(j, carry):
        d_dst, d_att = carry
        d_dst, d_att = list(d_dst), list(d_att)
        for h in range(heads):
            a = rows(a_ref, h, j)
            de = a * (rows(da_sc, h, j) - total[h]) * _slope(rows(e_ref, h,
                                                                 j))
            d_dst[h] = d_dst[h] + de
            a, de = _spread(a), _spread(de)
            for t, lane in enumerate(tiles[h]):
                dzs_ref[j, :, lane] = a * g_tile(h, t) + de * att_ref[:, lane]
                i = h * len(tiles[h]) + t
                d_att[i] = d_att[i] + jnp.sum(de * zs_ref[j, :, lane], axis=0,
                                              keepdims=True)
        return tuple(d_dst), tuple(d_att)

    zero = jnp.zeros((1, LANES), jnp.float32)
    d_dst, d_att = jax.lax.fori_loop(0, slots, second, (
        tuple(d_self), (zero,) * (lanes // LANES)))
    for h in range(heads):
        ddst_ref[pl.ds(h, 1), :] = d_dst[h]
    for i in range(lanes // LANES):
        datt_ref[:, i * LANES:(i + 1) * LANES] = d_att[i]


def _backward(z_t, z_s, att_lanes, a_self, a_nbr, e_self, e_nbr, g, concat):
    slots, rows, lanes = z_s.shape
    heads = a_self.shape[0]
    tb = row_block(slots, rows, lanes, heads, g.shape[-1])
    grid = (rows // tb,)
    slot_rows = pl.BlockSpec((slots, tb, lanes), lambda i: (0, i, 0))
    by_slot = pl.BlockSpec((slots, heads, tb), lambda i: (0, 0, i))
    own = pl.BlockSpec((heads, tb), lambda i: (0, i))
    target = pl.BlockSpec((tb, lanes), lambda i: (i, 0))
    return pl.pallas_call(
        functools.partial(_bwd_kernel, heads=heads, concat=concat),
        grid=grid,
        in_specs=[slot_rows, target,
                  pl.BlockSpec((tb, g.shape[-1]), lambda i: (i, 0)),
                  by_slot, by_slot, own, own,
                  pl.BlockSpec((1, lanes), lambda i: (0, 0))],
        out_specs=[slot_rows, target, own, own,
                   pl.BlockSpec((None, 1, lanes), lambda i: (i, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct(z_s.shape, jnp.float32),
                   jax.ShapeDtypeStruct(z_t.shape, jnp.float32),
                   jax.ShapeDtypeStruct(a_self.shape, jnp.float32),
                   jax.ShapeDtypeStruct(a_self.shape, jnp.float32),
                   jax.ShapeDtypeStruct((grid[0], 1, lanes), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((slots, heads, tb), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",), vmem_limit_bytes=VMEM_LIMIT),
        name="gat_pair_bwd",
    )(z_s, z_t, g, a_nbr, e_nbr, a_self, e_self, att_lanes)


def _forward(z_t, z_s, t_src, t_dst, att_src, mask, concat):
    slots, rows, lanes = z_s.shape
    heads = t_src.shape[0]
    src = jnp.einsum("fg,nf->gn", encoders._block_diagonal(att_src),
                     z_s.reshape(slots * rows, lanes))
    e_self = jax.nn.leaky_relu(t_src + t_dst, SLOPE)
    e_nbr = jax.nn.leaky_relu(src.reshape(heads, slots, rows) + t_dst[:, None],
                              SLOPE)
    a_self, a_nbr = encoders._slot_softmax(e_nbr, mask, e_self)
    y = encoders._weighted_sum(z_t, z_s, a_self, a_nbr, concat)
    return y, (a_self, a_nbr, e_self, e_nbr)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def attend(z_t: Array, z_s: Array, t_src: Array, t_dst: Array,
           att_src: Array, mask: Optional[Array], concat: bool) -> Array:
    """The pair's attention (module docstring): z_t [M, H*C] and z_s
    [k, M, H*C] projected rows, t_src / t_dst [H, M] the targets' own
    scores, att_src [C, H] the heads' source vectors, mask bool [k, M]
    (False at a pad slot) or None -> [M, H*C] (heads concatenated) or
    [M, C] (averaged). Its backward is the Pallas kernel (`row_block`
    must find a block); off the TPU it runs only under
    `pltpu.force_tpu_interpret_mode()` (the tests')."""
    return _forward(z_t, z_s, t_src, t_dst, att_src, mask, concat)[0]


def _attend_fwd(z_t, z_s, t_src, t_dst, att_src, mask, concat):
    y, (a_self, a_nbr, e_self, e_nbr) = _forward(z_t, z_s, t_src, t_dst,
                                                 att_src, mask, concat)
    # slots first: a slot's weights are one [H, M] tile
    return y, (z_t, z_s, att_src, a_self, jnp.swapaxes(a_nbr, 0, 1), e_self,
               jnp.swapaxes(e_nbr, 0, 1))


def _attend_bwd(concat, res, g):
    z_t, z_s, att_src, a_self, a_nbr, e_self, e_nbr = res
    att_lanes = att_src.T.reshape(1, -1)          # head h in lanes h*C..
    dz_s, dz_t, d_src, d_dst, d_att = _backward(
        z_t, z_s, att_lanes, a_self, a_nbr, e_self, e_nbr, g, concat)
    heads = a_self.shape[0]
    d_att = d_att.sum(axis=0).reshape(heads, -1).T
    return (dz_t.astype(z_t.dtype), dz_s.astype(z_s.dtype),
            d_src, d_dst, d_att.astype(att_src.dtype), None)


attend.defvjp(_attend_fwd, _attend_bwd)

