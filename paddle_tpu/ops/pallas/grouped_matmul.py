"""The grouped product of an expert layer as one Pallas TPU kernel.

`lhs` [M, K] holds group 0's rows first, then group 1's; `rhs`
[E, K, N] holds a matrix a group; `group_sizes` [E] says how many rows
each group has. Row r of the result is `lhs[r] @ rhs[group of r]`.

What the kernel walks is a list of VISITS, made on the device before it
starts and handed over as scalar-prefetch operands: a visit is one
group that has rows, in one tile of `tm` rows that holds some of them.
A group with no row has no visit, so its matrix is never named by the
weight block's index map and never leaves HBM; the tiles past the
groups' sum have no visit either, so what the kernel costs follows the
rows that are led, not M. The grid is (tiles of N, visits), the second
extent being the number of visits of THIS call (a dynamic grid; a call
whose groups are all empty takes one turn, which fetches the first
block and computes nothing).

A visit's weight block is `[K, tn]`: the whole contraction, and as
many columns as `WEIGHT_TILE_BYTES` allows, so that one DMA is some MB
and its time, not the grid step's, sets the pace; where N fits, a group's
matrix crosses HBM -> VMEM once, in one piece. Consecutive visits of one
group (a group that spans row tiles) name the same block, which Pallas
then does not fetch again. The rows of a tile and the result tile stay
in VMEM while the groups that share the tile are visited in turn; each
writes the rows that are its own and leaves the others as they were.
Rows that no group holds are never written: undefined, the caller masks
them.

Products take the operands' type with float32 accumulation. Runs
interpreted on the CPU backend, as `flash_attention.py` does.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _interpret
from .paged_attention import LANES

# rows a tile: what the MXU takes in one pass. A product of 16 rows still
# latches every 128 x 128 piece of the group's matrix, so a smaller tile
# saves the array nothing and only adds visits: in the decode steps of
# the three expert cells tiles of 16 to 128 rows read within 4% of one
# another, 128 ahead where a step has most groups (PERF.md, PR 36)
ROW_TILE = 128
# what one weight block may take of VMEM (there are two: the one in use
# and the one in flight). 16 MiB is 20 us of DMA at 819 GB/s against a
# grid step's 0.35 us
WEIGHT_TILE_BYTES = 16 * 2**20
# beside the blocks: the product's float32 result before it is stored,
# Mosaic's own scratch
VMEM_SLACK_BYTES = 8 * 2**20


def tiles(m, k, n, dtype):
    """(tm, tk, tn) for `lhs [m, k] x rhs [groups, k, n]` of `dtype`,
    from the shapes alone. tm: `ROW_TILE`, or all of a shorter `lhs` in
    whole sublane tiles. tk: all of k. tn: n in the fewest equal pieces
    of whole lane tiles that fit `WEIGHT_TILE_BYTES`."""
    itemsize = jnp.dtype(dtype).itemsize
    sublanes = 32 // itemsize
    tm = min(ROW_TILE, -(-m // sublanes) * sublanes)
    widest = max(LANES,
                 WEIGHT_TILE_BYTES // (k * itemsize) // LANES * LANES)
    if n <= widest:
        return tm, k, n
    pieces = -(-n // widest)
    return tm, k, -(-n // pieces // LANES) * LANES


def visits(group_sizes, m, tm):
    """The kernel's walk: (group [V], tile [V], first row [E], end row
    [E], number of visits [1]), all int32. V = tiles of m + E - 1 is
    the most there can be; entries past the number of visits are
    in range and unread."""
    e = group_sizes.shape[0]
    tiles_m = -(-m // tm)
    sizes = group_sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    first = starts // tm
    n_tiles = jnp.where(sizes > 0, (ends - 1) // tm - first + 1, 0)
    visit_ends = jnp.cumsum(n_tiles)
    v = jnp.arange(tiles_m + e - 1, dtype=jnp.int32)
    # visit v is of the first group whose visits end past v: a count of
    # [V, E] comparisons, one fusion where a binary search is a loop
    group = jnp.minimum(
        jnp.sum(visit_ends[None, :] <= v[:, None], axis=1, dtype=jnp.int32),
        e - 1)
    tile = first[group] + v - (visit_ends[group] - n_tiles[group])
    return (group, jnp.clip(tile, 0, tiles_m - 1).astype(jnp.int32),
            starts, ends, visit_ends[-1:])


def _kernel(group_ref, tile_ref, start_ref, end_ref, count_ref, lhs_ref,
            rhs_ref, out_ref, *, precision):
    v = pl.program_id(1)

    @pl.when(v < count_ref[0])
    def _visit():
        tm = lhs_ref.shape[0]
        g = group_ref[v]
        row = tile_ref[v] * tm + jax.lax.broadcasted_iota(
            jnp.int32, (tm, 1), 0)
        own = jnp.logical_and(row >= start_ref[g], row < end_ref[g])
        acc = jnp.dot(lhs_ref[...], rhs_ref[0], precision=precision,
                      preferred_element_type=jnp.float32)
        out_ref[...] = jnp.where(own, acc, out_ref[...])


def _note(lowered):
    """Trace time: what a grouped product lowered to, for the step log
    (the flight recorder, `kind` "moe.grouped_product") and, with the
    monitor on, the counter of that name."""
    from ...monitor import STAT_ADD, flight_record
    STAT_ADD("moe.grouped_product")
    flight_record("moe.grouped_product", lowered=lowered)


@jax.jit
def _product(lhs, rhs, group_sizes):
    """Jitted, so that the layers of one program (same shapes) share one
    trace and one lowering of the walk and the kernel."""
    m, k = lhs.shape
    n = rhs.shape[2]
    tm, tk, tn = tiles(m, k, n, lhs.dtype)
    group, tile, starts, ends, count = visits(group_sizes, m, tm)
    itemsize = jnp.dtype(lhs.dtype).itemsize
    vmem = 2 * (tk * tn + tm * tk) * itemsize + 4 * tm * tn * 4 \
        + VMEM_SLACK_BYTES
    block = functools.partial(pl.BlockSpec, memory_space=pltpu.VMEM)
    return pl.pallas_call(
        functools.partial(
            _kernel, precision=jax.lax.Precision.HIGHEST
            if lhs.dtype == jnp.float32 else None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            # all groups empty: one turn that does nothing
            grid=(-(-n // tn), jnp.maximum(count[0], 1)),
            in_specs=[
                block((tm, tk), lambda j, v, g, t, *_: (t[v], 0)),
                block((1, tk, tn), lambda j, v, g, t, *_: (g[v], 0, j)),
            ],
            out_specs=block((tm, tn), lambda j, v, g, t, *_: (t[v], j))),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        interpret=_interpret(),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(pltpu.GridDimensionSemantics.ARBITRARY,
                                 pltpu.GridDimensionSemantics.ARBITRARY),
            vmem_limit_bytes=vmem),
        name="grouped_matmul",
    )(group, tile, starts, ends, count, lhs, rhs)


def _forward(lhs, rhs, group_sizes):
    tm, tk, tn = tiles(*lhs.shape, rhs.shape[2], lhs.dtype)
    _note(f"pallas[{tm}, {tk}, {tn}]")
    return _product(lhs, rhs, group_sizes)


@jax.custom_vjp
def grouped_matmul(lhs, rhs, group_sizes):
    """`lhs` [M, K] x `rhs` [E, K, N] by groups of rows: rows
    [sum(group_sizes[:g]), sum(group_sizes[:g + 1])) of the result are
    those rows of `lhs` times `rhs[g]`, in float32. The rows past the
    groups' sum are left unwritten (undefined). Differentiable in `lhs`
    and `rhs`: the backward products are `jax.lax.ragged_dot`'s."""
    return _forward(lhs, rhs, group_sizes)


def _fwd(lhs, rhs, group_sizes):
    return _forward(lhs, rhs, group_sizes), (lhs, rhs, group_sizes)


def _bwd(saved, g):
    lhs, rhs, group_sizes = saved
    _note("ragged_dot")
    # the rows past the groups' sum were never written, and what came of
    # them downstream may be anything (0 x NaN): they take no part
    led = jnp.arange(lhs.shape[0])[:, None] < jnp.sum(group_sizes)
    _, pull = jax.vjp(
        lambda l, r: jax.lax.ragged_dot(
            l, r, group_sizes, preferred_element_type=jnp.float32),
        jnp.where(led, lhs, 0), rhs)
    return (*pull(jnp.where(led, g, 0)), None)


grouped_matmul.defvjp(_fwd, _bwd)
