"""Engine-facing sharding layer for CJT execution over a device mesh.

Row sharding (the engine path)
------------------------------
Semiring ⊕ is associative, so a bag contraction row-shards cleanly: split
the fact relation's rows across a 1-D mesh axis (dimension relations and
incoming γ-indexed messages stay replicated), run the rowwise lift →
σ-mask → ``segment_aggregate`` pipeline per shard, and ⊕-all-reduce the
γ-indexed partials — ``psum`` for rings with leafwise + (SUM/COUNT/
MOMENTS), ``pmin``/``pmax`` for the tropical rings.  Every cross-shard
message is a tiny ``(|γ|, V)`` collective; nothing ever materializes a
join.  :mod:`repro.core.plans` builds the sharded plans; this module owns
mesh acquisition, the ring → collective mapping, and the row placement
helpers.

All acquisition is lazy: importing this module must never touch devices
(CI hosts without a mesh import it fine).

Domain sharding (chain demo)
----------------------------
The original seed demo below shards factors along one attribute's *domain*
instead: forward messages marginalize the sharded attribute (local partial
contraction + ``psum_scatter``), backward messages marginalize the
replicated one (``all_gather`` + local contraction) — r-1 reduce-scatters
+ r-1 all-gathers per calibration pass over a chain of r factors.  It is
kept as a collective-schedule reference; the engine uses row sharding.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# Name of the 1-D mesh axis the engine row-shards over.
SHARD_AXIS = "shard"

def shard_devices() -> int:
    """Mesh width requested via ``REPRO_SHARD_DEVICES`` (0/1/unset → off)."""
    try:
        n = int(os.environ.get("REPRO_SHARD_DEVICES", "0"))
    except ValueError:
        return 0
    return n if n > 1 else 0


def make_engine_mesh(devices: int | None = None) -> Mesh | None:
    """The engine's 1-D row-shard mesh over the first ``devices`` devices.

    ``devices=None`` reads ``REPRO_SHARD_DEVICES``; a width of 0 or 1 means
    no mesh (``None``, run unsharded).  Asking for more devices than the
    backend has raises: a mesh that silently shrank would hide the devices a
    deployment expects.  CPU hosts provide virtual devices via
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N``.
    """
    n = shard_devices() if devices is None else int(devices)
    if n <= 1:
        return None
    have = jax.device_count()
    if have < n:
        raise ValueError(f"a {n}-device mesh needs {n} devices, the backend has {have}")
    return jax.make_mesh(
        (n,), (SHARD_AXIS,), axis_types=(jax.sharding.AxisType.Auto,),
        devices=jax.devices()[:n],
    )


def ring_collective(ring):
    """⊕-all-reduce for a ring's γ-indexed partials, or ``None``.

    ``None`` means the ring's ⊕ has no mesh collective here (BOOL: ⊕ = ∨)
    and callers must fall back to the unsharded plan.
    """
    op = getattr(ring, "kernel_segment_op", None)
    if op == "min":
        return jax.lax.pmin
    if op == "max":
        return jax.lax.pmax
    if op == "sum" or getattr(ring, "has_add_inverse", False):
        return jax.lax.psum
    return None


def allreduce_field(field, collective, axis: str = SHARD_AXIS):
    """⊕-all-reduce every leaf of a field/Factor pytree over ``axis``."""
    return jax.tree_util.tree_map(lambda leaf: collective(leaf, axis), field)


def row_placement(mesh: Mesh, axis: str = SHARD_AXIS) -> NamedSharding:
    """Sharding that splits leading-axis rows across the mesh (rest replicated)."""
    return NamedSharding(mesh, P(axis))


def place_rows(field, mesh: Mesh, axis: str = SHARD_AXIS):
    """Commit every leaf of a row-major pytree to the row-shard placement.

    Pre-placing cached row arrays (flat codes, padded lifts) means jit'd
    sharded plans consume them without a per-dispatch reshard copy.
    """
    sh = row_placement(mesh, axis)
    return jax.tree_util.tree_map(lambda leaf: jax.device_put(leaf, sh), field)


def shard_imbalance(num_rows: int, bucket: int, nshards: int) -> float:
    """Max valid rows per shard / ideal per-shard rows (≥ 1.0 when nonempty).

    Rows are packed low (pad rows carry the ⊕-identity at the top of the
    bucket), so the fullest shard is the first block.
    """
    if nshards <= 1 or num_rows <= 0:
        return 1.0 if num_rows > 0 else 0.0
    block = bucket // nshards
    return min(block, num_rows) * nshards / num_rows


# --------------------------------------------------------------------------
# Domain-sharded chain demo (seed reference; see module docstring)
# --------------------------------------------------------------------------


def calibrate_chain_reference(factors: list[jax.Array]) -> tuple[list, list]:
    """Single-device oracle: forward/backward messages of a chain CJT.

    factors[i]: (d_i, d_{i+1}) arithmetic-ring factor between A_i and A_{i+1}.
    Returns (fwd, bwd): fwd[i] over A_{i+1} (message bag_i→bag_{i+1}),
    bwd[i] over A_{i+1} (message bag_{i+1}→bag_i).
    """
    r = len(factors)
    fwd, bwd = [None] * (r - 1), [None] * (r - 1)
    m = jnp.ones((factors[0].shape[0],), factors[0].dtype)
    for i in range(r - 1):
        m = m @ factors[i]              # Σ_{A_i} F_i ⊗ m   → over A_{i+1}
        fwd[i] = m
    m = jnp.ones((factors[-1].shape[1],), factors[0].dtype)
    for i in range(r - 2, -1, -1):
        m = factors[i + 1] @ m          # Σ_{A_{i+2}} F_{i+1} ⊗ m → over A_{i+1}
        bwd[i] = m
    return fwd, bwd


def chain_absorptions_reference(factors, fwd, bwd):
    """Absorption at every bag: the calibrated per-bag views."""
    r = len(factors)
    out = []
    for i in range(r):
        f = factors[i]
        if i > 0:
            f = f * fwd[i - 1][:, None]
        if i < r - 1:
            f = f * bwd[i][None, :]
        out.append(f)
    return out


def make_chain_calibrate(mesh: Mesh, axis: str, r: int, d: int, dtype=jnp.float32):
    """Build a jitted sharded calibration fn for a chain of r (d,d) factors.

    Sharding: factor i is (A_i sharded, A_{i+1} replicated); every message is
    sharded along its own attribute.
    """
    n = mesh.shape[axis]
    assert d % n == 0, f"domain {d} not divisible by mesh axis {n}"

    def _local(factors):
        # factors: list of local blocks (d/n, d)
        fwd = []
        m = jnp.ones((d // n,), dtype)
        for i in range(r - 1):
            partial_msg = m @ factors[i]                       # (d,) partial over local A_i rows
            m = jax.lax.psum_scatter(
                partial_msg, axis, scatter_dimension=0, tiled=True
            )                                                   # (d/n,) over A_{i+1}
            fwd.append(m)
        bwd = []
        m = jnp.ones((d // n,), dtype)
        for i in range(r - 2, -1, -1):
            full = jax.lax.all_gather(m, axis, tiled=True)      # (d,) over A_{i+2}
            m = factors[i + 1] @ full                           # (d/n,) over A_{i+1}
            bwd.append(m)
        bwd = bwd[::-1]
        # total-count absorption at bag 0 (scalar sanity output)
        full_b = jax.lax.all_gather(bwd[0], axis, tiled=True) if r > 1 else None
        f0 = factors[0]
        total_local = (
            jnp.sum(f0 @ full_b) if full_b is not None else jnp.sum(f0)
        )
        total = jax.lax.psum(total_local, axis)
        return fwd, bwd, total

    shard_spec = P(axis, None)
    msg_spec = P(axis)
    fn = jax.shard_map(
        _local,
        mesh=mesh,
        in_specs=([shard_spec] * r,),
        out_specs=([msg_spec] * (r - 1), [msg_spec] * (r - 1), P()),
    )
    return jax.jit(fn)


def make_chain_calibrate_multi(mesh: Mesh, axis: str, r: int, d: int,
                               n_measures: int, dtype=jnp.float32):
    """Beyond-paper: fuse V measure semirings into ONE calibration pass.

    The paper materializes messages per aggregate (one SPJA query each).
    Stacking the V annotation columns turns every message matvec into a
    (d/n, d)×(d, V) matmul: factors are read from HBM once instead of V
    times (memory term ÷V) and the MXU gets a real contraction.  Messages
    and collectives carry (d/n, V) blocks.

    Factor annotations: (d/n, d) structural counts shared by all measures;
    per-measure leaf annotations enter at bag 0 as a (d/n, V) block.
    """
    n = mesh.shape[axis]
    assert d % n == 0

    def _local(factors, leaf_vals):
        fwd = []
        m = leaf_vals                                        # (d/n, V)
        for i in range(r - 1):
            partial_msg = jnp.einsum("kv,kd->dv", m, factors[i])
            m = jax.lax.psum_scatter(partial_msg, axis, scatter_dimension=0, tiled=True)
            fwd.append(m)                                    # (d/n, V)
        bwd = []
        m = jnp.ones((d // n, n_measures), dtype)
        for i in range(r - 2, -1, -1):
            full = jax.lax.all_gather(m, axis, tiled=True)   # (d, V)
            m = factors[i + 1] @ full                        # (d/n, V)
            bwd.append(m)
        bwd = bwd[::-1]
        # absorption at the last bag: ⊕ over its own factor too
        total_local = jnp.einsum("kv,k->v", fwd[-1], factors[-1].sum(axis=1))
        totals = jax.lax.psum(total_local, axis)
        return fwd, bwd, totals

    msg_spec = P(axis, None)
    fn = jax.shard_map(
        _local,
        mesh=mesh,
        in_specs=([P(axis, None)] * r, P(axis, None)),
        out_specs=([msg_spec] * (r - 1), [msg_spec] * (r - 1), P()),
    )
    return jax.jit(fn)


def chain_multi_specs(mesh: Mesh, axis: str, r: int, d: int, n_measures: int,
                      dtype=jnp.float32):
    sh = NamedSharding(mesh, P(axis, None))
    factors = [jax.ShapeDtypeStruct((d, d), dtype, sharding=sh) for _ in range(r)]
    leaf = jax.ShapeDtypeStruct((d, n_measures), dtype, sharding=sh)
    return factors, leaf


def place_chain_factors(mesh: Mesh, axis: str, factors_np: list[np.ndarray]):
    sh = NamedSharding(mesh, P(axis, None))
    return [jax.device_put(jnp.asarray(f), sh) for f in factors_np]


def chain_factor_specs(mesh: Mesh, axis: str, r: int, d: int, dtype=jnp.float32):
    """ShapeDtypeStructs for the dry-run (no allocation)."""
    sh = NamedSharding(mesh, P(axis, None))
    return [jax.ShapeDtypeStruct((d, d), dtype, sharding=sh) for _ in range(r)]
