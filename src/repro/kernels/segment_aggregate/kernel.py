"""Pallas TPU kernel: sparse → dense segment aggregation (one-hot matmul).

The leaf step of every CJT message over a fact table: N dictionary-encoded
rows with per-row annotation vectors collapse into a dense (G, V) factor.
TPUs have no efficient random scatter, so the DBMS hash-aggregate is
re-thought for the MXU: each (TG groups × TN rows) tile builds the one-hot
membership matrix ``group_ids[g] == codes[n]`` and contracts it with the
value slab — turning data-dependent scatter into dense matmul.

  out[g, v] ⊕= Σ_n  1[codes[n] == g] · values[v, n]      (⊕ ∈ {sum, min, max})

Layout: rows run along the 128-wide lane axis of every operand.  Codes are
one ``(1, N)`` int32 row and values a ``(V, N)`` slab, so a row tile is a
``(1, TN)`` / ``(V, TN)`` block and the one-hot is ``(TG, TN)`` — a sublane
iota compared against the lane-dense code row.  SUM contracts it with the
value slab on the MXU (rows contracted, f32 at full precision); MIN/MAX
reduce one value row at a time over a 2-D ``where`` along the lanes.
Output tiles are ``(TG, V)``.

Grid: (G/TG, N/TN) with rows innermost (accumulation), so each output tile
stays resident in VMEM across the row stream.

``level_segment_aggregate`` extends this to a *multi-segment* launch: all
independent messages of one calibration level share a single block-diagonal
grid (per-message ``(offset, num_segments)`` descriptors become a static
work-tile table), so a whole level costs one kernel dispatch.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import mode

DEFAULT_TN = 2048   # rows per tile: a multiple of the 128-lane width
DEFAULT_TG = 128    # groups per tile: a multiple of the 8-sublane height

IDENTITY = {"sum": 0.0, "min": jnp.inf, "max": -jnp.inf}


def _accumulate(codes_ref, vals_ref, o_ref, g0, *, op: str, tg: int):
    """⊕-accumulate one (row tile × group tile) into the (TG, V) output."""
    codes = codes_ref[...]                            # (1, TN) int32
    vals = vals_ref[...].astype(jnp.float32)          # (V, TN)
    tn = codes.shape[1]
    gids = g0 + jax.lax.broadcasted_iota(jnp.int32, (tg, tn), 0)
    onehot = gids == codes                            # (TG, TN) bool
    if op == "sum":
        o_ref[...] += jax.lax.dot_general(
            onehot.astype(jnp.float32), vals,
            (((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        )                                             # (TG, V)
        return
    init = IDENTITY[op]
    reduce = jnp.min if op == "min" else jnp.max
    combine = jnp.minimum if op == "min" else jnp.maximum
    for j in range(vals.shape[0]):
        red = reduce(jnp.where(onehot, vals[j:j + 1, :], init), axis=1,
                     keepdims=True)                   # (TG, 1)
        o_ref[:, j:j + 1] = combine(o_ref[:, j:j + 1], red)


def _kernel(codes_ref, vals_ref, o_ref, *, op: str, tg: int):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        o_ref[...] = jnp.full_like(o_ref, IDENTITY[op])

    _accumulate(codes_ref, vals_ref, o_ref, pl.program_id(0) * tg, op=op, tg=tg)


def segment_aggregate(
    codes: jax.Array,      # (1, N) int32 group ids in [0, G); others match none
    values: jax.Array,     # (V, N) row annotations, rows along the last axis
    num_segments: int,
    op: str = "sum",
    tn: int = DEFAULT_TN,
    tg: int = DEFAULT_TG,
    interpret: bool | None = None,
) -> jax.Array:
    """(G, V) ⊕-aggregate; N must divide by ``tn`` and G by ``tg``."""
    v, n = values.shape
    g = num_segments
    assert codes.shape == (1, n), (codes.shape, values.shape)
    assert n % tn == 0 and g % tg == 0, (n, g, tn, tg)
    return pl.pallas_call(
        functools.partial(_kernel, op=op, tg=tg),
        grid=(g // tg, n // tn),
        in_specs=[
            pl.BlockSpec((1, tn), lambda i, k: (0, k)),
            pl.BlockSpec((v, tn), lambda i, k: (0, k)),
        ],
        out_specs=pl.BlockSpec((tg, v), lambda i, k: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((g, v), jnp.float32),
        interpret=mode.resolve_interpret(interpret),
        name=f"segment_aggregate_{op}",
    )(codes, values)


# ---------------------------------------------------------------------------
# level kernel: many independent segment aggregations in ONE launch
# ---------------------------------------------------------------------------

def _level_kernel(row_ref, seg_ref, start_ref, first_ref, codes_ref, vals_ref,
                  o_ref, *, op: str, tg: int):
    del row_ref, seg_ref  # consumed by the index maps only
    i = pl.program_id(0)

    @pl.when(first_ref[i] == 1)
    def _init():
        o_ref[...] = jnp.full_like(o_ref, IDENTITY[op])

    # codes are GLOBAL segment ids; pad rows (-1) match nothing → identity
    _accumulate(codes_ref, vals_ref, o_ref, start_ref[i], op=op, tg=tg)


def level_segment_aggregate(
    codes: jax.Array,              # (1, ΣN_j) int32 GLOBAL segment ids; pad rows -1
    values: jax.Array,             # (V, ΣN_j) row slabs, row-padded to common V
    row_blocks: jax.Array,         # (T,) int32 per-tile input row-block index
    seg_blocks: jax.Array,         # (T,) int32 per-tile output segment block
    tile_start: jax.Array,         # (T,) int32: first global id of each tile
    tile_first: jax.Array,         # (T,) int32: 1 → first row tile for its block
    total_segments: int,           # ΣG_j (tile-aligned)
    op: str = "sum",
    tn: int = DEFAULT_TN,
    tg: int = DEFAULT_TG,
    interpret: bool | None = None,
) -> jax.Array:
    """One grid over the block-diagonal union of several segment reductions.

    The fused 'level kernel' behind one-launch-per-calibration-level: each
    same-level message j contributes a (rows_j, segs_j) aggregation whose row
    and segment ranges are tile-aligned and disjoint in the concatenated
    operands.  A 1-D grid walks a work-tile table held in scalar-prefetch
    memory (index maps pick each tile's row/segment block from it) — for
    every message, rows innermost per output tile so each (TG, V) block stays
    resident across its row stream — and no (row tile, segment tile) pair
    from *different* messages ever meets, so total work stays Σ_j N_j·G_j·V
    instead of (ΣN)(ΣG)V.
    """
    v, n = values.shape
    t = row_blocks.shape[0]
    assert codes.shape == (1, n), (codes.shape, values.shape)
    assert seg_blocks.shape[0] == t and n % tn == 0 and total_segments % tg == 0
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(t,),
        in_specs=[
            pl.BlockSpec((1, tn), lambda i, row, seg, st, ft: (0, row[i])),
            pl.BlockSpec((v, tn), lambda i, row, seg, st, ft: (0, row[i])),
        ],
        out_specs=pl.BlockSpec((tg, v), lambda i, row, seg, st, ft: (seg[i], 0)),
    )
    return pl.pallas_call(
        functools.partial(_level_kernel, op=op, tg=tg),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((total_segments, v), jnp.float32),
        interpret=mode.resolve_interpret(interpret),
        name=f"level_segment_aggregate_{op}",
    )(row_blocks, seg_blocks, tile_start, tile_first, codes, values)
