"""Pallas TPU kernel: arithmetic-semiring contraction (the CJT message hot path).

Computes  C[g, a] = Σ_b  M[g, b] ⊗ R[b, a]   over the (+, ×) ring, with an
optional fused σ mask on the contracted (separator) axis — i.e. one message
step ``⊕_b (incoming ⊗ bag)`` with selection push-down, as an MXU matmul.

Tiling: (TG, TB) × (TB, TA) blocks in VMEM, fp32 accumulation in the output
block; grid is (G/TG, A/TA, B/TB) with the contraction dimension innermost so
each output tile is initialized at b==0 and accumulated across b steps.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import mode


DEFAULT_TILES = (128, 128, 128)  # (TG, TB, TA) — MXU-aligned


def _kernel(m_ref, r_ref, mask_ref, o_ref):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    m = m_ref[...].astype(jnp.float32)
    if mask_ref is not None:
        m = m * mask_ref[...].astype(jnp.float32)     # (1, TB) row mask
    r = r_ref[...].astype(jnp.float32)
    o_ref[...] += jnp.dot(m, r, precision=jax.lax.Precision.HIGHEST,
                          preferred_element_type=jnp.float32)


def semiring_contract(
    m: jax.Array,                  # (G, B)
    r: jax.Array,                  # (B, A)
    mask: jax.Array | None = None,  # (1, B) 0/1 σ mask on the contracted axis
    tiles: tuple[int, int, int] = DEFAULT_TILES,
    interpret: bool | None = None,
) -> jax.Array:
    g, b = m.shape
    b2, a = r.shape
    assert b == b2, (m.shape, r.shape)
    tg, tb, ta = (min(tiles[0], g), min(tiles[1], b), min(tiles[2], a))
    assert g % tg == 0 and b % tb == 0 and a % ta == 0, (m.shape, r.shape, tiles)
    grid = (g // tg, a // ta, b // tb)

    in_specs = [
        pl.BlockSpec((tg, tb), lambda i, j, k: (i, k)),
        pl.BlockSpec((tb, ta), lambda i, j, k: (k, j)),
    ]
    args = [m, r]
    if mask is not None:
        assert mask.shape == (1, b), (mask.shape, m.shape)
        in_specs.append(pl.BlockSpec((1, tb), lambda i, j, k: (0, k)))
        args.append(mask)
    return pl.pallas_call(
        _kernel if mask is not None else _masked_none_kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((tg, ta), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((g, a), jnp.float32),
        interpret=mode.resolve_interpret(interpret),
        name="semiring_contract",
    )(*args)


def _masked_none_kernel(m_ref, r_ref, o_ref):
    _kernel(m_ref, r_ref, None, o_ref)
