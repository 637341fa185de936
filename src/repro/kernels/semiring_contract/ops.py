"""Jit'd wrapper for semiring_contract with shape padding.

The kernel compiles on a TPU backend and runs interpreted elsewhere
(``repro.kernels.mode``).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .kernel import semiring_contract, DEFAULT_TILES


def _pad_to(x, mult, axis):
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x, 0
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), pad


@partial(jax.jit, static_argnames=("interpret",))
def contract_op(m, r, mask=None, interpret: bool | None = None):
    """Padded semiring contraction; returns (G, A) fp32."""
    g, b = m.shape
    a = r.shape[1]
    tg = min(DEFAULT_TILES[0], max(8, g))
    tb = min(DEFAULT_TILES[1], max(8, b))
    ta = min(DEFAULT_TILES[2], max(8, a))
    mp, _ = _pad_to(m, tg, 0)
    mp, _ = _pad_to(mp, tb, 1)
    rp, _ = _pad_to(r, tb, 0)
    rp, _ = _pad_to(rp, ta, 1)
    mk = None
    if mask is not None:
        mk, _ = _pad_to(mask.astype(jnp.float32)[None, :], tb, 1)
    out = semiring_contract(mp, rp, mk, tiles=(tg, tb, ta), interpret=interpret)
    return out[:g, :a]

