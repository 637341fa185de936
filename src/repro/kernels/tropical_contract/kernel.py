"""Pallas TPU kernel: tropical (min,+)/(max,+) contraction.

C[g, a] = min_b ( M[g, b] + R[b, a] )   (or max).

MIN/MAX semiring messages (e.g. Fig 21's MAX(COUNT) over the empty bag)
cannot use the MXU — this is a VPU kernel: each (TG, TA) output tile
accumulates a broadcast-add/reduce over TB-sized slabs of the contracted
axis, so VMEM holds one (TG, TB, TA) intermediate at a time.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import mode

DEFAULT_TILES = (16, 128, 128)  # (TG, TB, TA): (16·128·128)·4B = 1 MiB slab


def _kernel(m_ref, r_ref, o_ref, *, is_min: bool):
    init = jnp.inf if is_min else -jnp.inf

    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.full_like(o_ref, init)

    m = m_ref[...].astype(jnp.float32)          # (TG, TB)
    r = r_ref[...].astype(jnp.float32)          # (TB, TA)
    slab = m[:, :, None] + r[None, :, :]        # (TG, TB, TA)
    red = jnp.min(slab, axis=1) if is_min else jnp.max(slab, axis=1)
    cur = o_ref[...]
    o_ref[...] = jnp.minimum(cur, red) if is_min else jnp.maximum(cur, red)


def tropical_contract(
    m: jax.Array,                   # (G, B)
    r: jax.Array,                   # (B, A)
    is_min: bool = True,
    tiles: tuple[int, int, int] = DEFAULT_TILES,
    interpret: bool | None = None,
) -> jax.Array:
    g, b = m.shape
    b2, a = r.shape
    assert b == b2
    tg, tb, ta = (min(tiles[0], g), min(tiles[1], b), min(tiles[2], a))
    assert g % tg == 0 and b % tb == 0 and a % ta == 0
    grid = (g // tg, a // ta, b // tb)
    return pl.pallas_call(
        functools.partial(_kernel, is_min=is_min),
        grid=grid,
        in_specs=[
            pl.BlockSpec((tg, tb), lambda i, j, k: (i, k)),
            pl.BlockSpec((tb, ta), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((tg, ta), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((g, a), jnp.float32),
        interpret=mode.resolve_interpret(interpret),
        name="tropical_contract",
    )(m, r)
