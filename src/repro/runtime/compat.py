"""Small helpers over the installed jax's mesh and cost-analysis API.

- ``make_mesh`` builds a mesh whose axes are all Auto (or all Explicit).
- ``cost_analysis``/``compiled_flops`` read ``Compiled.cost_analysis()``,
  a dict of XLA's cost estimates, as a plain (possibly empty) dict.
"""

from __future__ import annotations

from typing import Mapping, Sequence

import jax


def make_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str], *,
              explicit: bool = False) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with every axis Auto (or Explicit)."""
    kind = jax.sharding.AxisType.Explicit if explicit else jax.sharding.AxisType.Auto
    return jax.make_mesh(tuple(axis_shapes), tuple(axis_names),
                         axis_types=(kind,) * len(axis_names))


def cost_analysis(compiled) -> Mapping[str, float]:
    """``Compiled.cost_analysis()`` as a dict (empty when XLA gives none)."""
    return dict(compiled.cost_analysis() or {})


def compiled_flops(compiled) -> float:
    return float(cost_analysis(compiled).get("flops", 0.0))
