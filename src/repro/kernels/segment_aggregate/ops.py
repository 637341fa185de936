"""Jit'd wrapper for segment_aggregate with row/group padding.

Sharded composition: both :func:`aggregate_op` and :func:`level_aggregate`
are *shard-local* — inside ``shard_map`` they see the shard's row block
(codes and value slab sliced on the leading axis; segment ids stay global)
and produce a full ``(num_segments, v)`` partial that the caller must
⊕-all-reduce over the mesh axis (``psum``/``pmin``/``pmax``; see
``repro.core.distributed.ring_collective``).  ⊕-identity row padding makes
any equal block split of a padded row bucket exact, and the enclosing
``jax.shard_map`` needs ``check_vma=False`` (jax has no replication rule
for ``pallas_call``).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .kernel import (
    segment_aggregate,
    level_segment_aggregate,
    DEFAULT_TG,
    DEFAULT_TN,
    IDENTITY,
)


def _round_up(x: int, m: int) -> int:
    return -(-max(x, 1) // m) * m


def _tiles(n: int, g: int) -> tuple[int, int]:
    """Row tile (a multiple of the 128 lanes) and group tile (of 8 sublanes)."""
    return min(DEFAULT_TN, _round_up(n, 128)), min(DEFAULT_TG, _round_up(g, 8))


@partial(jax.jit, static_argnames=("num_segments", "op", "interpret", "lane_major"))
def aggregate_op(codes, values, num_segments: int, op: str = "sum",
                 interpret: bool | None = None, lane_major: bool = False):
    """(G, V) ⊕-aggregate of an (N, V) slab (or (G,) of an (N,) vector).

    ``lane_major``: the slab is (V, N), rows on the lanes as the kernel
    takes them, and goes in without a transpose."""
    n = codes.shape[0]
    squeeze = values.ndim == 1
    if squeeze:
        values = values[None, :]
    elif not lane_major:
        values = values.T
    tn, tg = _tiles(n, num_segments)
    pad_n = (-n) % tn
    pad_g = (-num_segments) % tg
    # pad rows carry code -1, which matches no segment
    codes = jnp.pad(codes.astype(jnp.int32), (0, pad_n), constant_values=-1)
    values = jnp.pad(values.astype(jnp.float32), ((0, 0), (0, pad_n)))
    out = segment_aggregate(
        codes[None, :], values, num_segments + pad_g, op=op, tn=tn, tg=tg,
        interpret=interpret,
    )[:num_segments]
    return out[:, 0] if squeeze else out


def level_aggregate(items, op: str = "sum", interpret: bool | None = None,
                    lane_major: bool = False):
    """Fuse several independent ``(codes, values, num_segments)`` segment
    reductions into ONE ``level_segment_aggregate`` launch.

    Each item j is one same-level message: ``codes`` (n_j,) int32 local
    segment ids in [0, g_j), ``values`` (n_j, v_j) row slab, or (v_j, n_j)
    with ``lane_major`` (rows on the lanes, no transpose).  Rows are padded
    to the tile multiple with code -1 (matches no segment), columns to the
    common width and segments to the tile multiple with the ⊕-identity; local
    ids shift by the running segment offset so the concatenated launch is
    block-diagonal.  Returns the per-item (g_j, v_j) dense outputs.

    Traced helper — call it inside a jitted plan (shapes are static there);
    eager calls work too via pallas interpret mode.
    """
    assert items, "level_aggregate of zero messages"
    ident = IDENTITY[op]
    if not lane_major:
        items = [(c, v.T, g) for c, v, g in items]
    v_max = max(v.shape[0] for _, v, _ in items)
    tn, tg = _tiles(max(c.shape[0] for c, _, _ in items),
                    max(g for _, _, g in items))
    all_codes, all_vals = [], []
    row_blocks, seg_blocks, tile_start, tile_first = [], [], [], []
    row_off = seg_off = 0
    spans = []
    for codes, values, g in items:
        n = codes.shape[0]
        pad_n = (-n) % tn
        pad_g = (-g) % tg
        codes = codes.astype(jnp.int32) + seg_off
        if pad_n:
            codes = jnp.concatenate([codes, jnp.full((pad_n,), -1, jnp.int32)])
        if values.shape[0] < v_max or pad_n:
            values = jnp.pad(
                values,
                ((0, v_max - values.shape[0]), (0, pad_n)),
                constant_values=ident,
            )
        all_codes.append(codes)
        all_vals.append(values.astype(jnp.float32))
        n_blocks = (n + pad_n) // tn
        g_blocks = (g + pad_g) // tg
        for s in range(g_blocks):
            for r in range(n_blocks):
                row_blocks.append(row_off // tn + r)
                seg_blocks.append(seg_off // tg + s)
                tile_start.append(seg_off + s * tg)
                tile_first.append(1 if r == 0 else 0)
        spans.append((seg_off, g))
        row_off += n + pad_n
        seg_off += g + pad_g
    out = level_segment_aggregate(
        jnp.concatenate(all_codes)[None, :],
        jnp.concatenate(all_vals, axis=1),
        jnp.asarray(row_blocks, jnp.int32),
        jnp.asarray(seg_blocks, jnp.int32),
        jnp.asarray(tile_start, jnp.int32),
        jnp.asarray(tile_first, jnp.int32),
        seg_off,
        op=op,
        tn=tn,
        tg=tg,
        interpret=interpret,
    )
    return [
        out[off : off + g, : v.shape[0]]
        for (off, g), (_, v, _) in zip(spans, items)
    ]

