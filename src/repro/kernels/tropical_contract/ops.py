"""Jit'd wrapper for tropical_contract with identity-padding."""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .kernel import tropical_contract, DEFAULT_TILES


def _pad_to(x, mult, axis, value):
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


@partial(jax.jit, static_argnames=("is_min", "interpret"))
def contract_op(m, r, is_min: bool = True, interpret: bool | None = None):
    g, b = m.shape
    a = r.shape[1]
    ident = jnp.inf if is_min else -jnp.inf  # ⊕-identity pads the contracted axis
    tg = min(DEFAULT_TILES[0], max(8, g))
    tb = min(DEFAULT_TILES[1], max(8, b))
    ta = min(DEFAULT_TILES[2], max(8, a))
    mp = _pad_to(_pad_to(m, tg, 0, ident), tb, 1, ident)
    rp = _pad_to(_pad_to(r, tb, 0, ident), ta, 1, ident)
    # note: inf + -inf cannot occur — both operands pad with the same sign
    out = tropical_contract(mp, rp, is_min=is_min, tiles=(tg, tb, ta), interpret=interpret)
    return out[:g, :a]

