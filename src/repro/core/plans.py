"""Compiled message plans: structural jit + Pallas fast paths for bag contraction.

Every CJT message is one *bag contraction*: ⊗ the bag's lifted relation with
the incoming messages, apply σ, ⊕-marginalize to the separator ∪ carried γ.
The legacy engine executed that op-by-op — un-jitted JAX dispatches plus
host-side numpy index building (``np.ravel_multi_index``, row-mask gathers)
on *every* call.  This module compiles each contraction once and re-executes
it at hardware speed:

- **Structural plan keys.**  Plans are keyed by the contraction's *structure*
  (relation attr order/domains/row count, incoming-factor shapes, ring,
  out_attrs, predicate arity) — NOT by Proposition-2 signatures.  A new
  relation version, a different predicate mask, or a delta-maintenance pass
  changes the Prop-2 signature but not the structure, so it re-executes the
  already-compiled plan (trace once, run forever).
- **Device-resident inputs.**  Flat row codes live in ``Catalog.dev_flat_codes``
  (keyed ``(relation, version, attr-tuple)``); per-row lifts and densified
  base factors are cached here.  The message hot path does no host work
  beyond dict lookups, so upward/downward passes dispatch asynchronously and
  the engine only blocks at absorption.
- **Pallas routing.**  Inside the traced plan, the ⊕-segment reduction of
  f32 scalar rings (SUM/COUNT via ``kernel_segment_op="sum"``, tropical
  MIN/MAX via ``"min"``/``"max"``) lowers to the ``segment_aggregate`` Pallas
  kernel, and the 2-factor dense contraction of arithmetic rings lowers to
  the ``semiring_contract`` Pallas kernel (interpreted off-TPU).  Compound
  rings (MOMENTS, covariance, BOOL, int64 COUNT) keep the lax fallback.
  Off-TPU the one-hot-matmul kernels do O(N·G) work, so they are cost-gated
  (``REPRO_PLAN_KERNEL_COST``): small bags exercise the kernels, huge fact
  bags stay on the O(N) lax path until a real TPU is attached.
- **Batched plans.**  A crossfilter event fans one interaction out to every
  linked viz, and each viz's warm-path work collapses to a single absorption
  at the σ'd bag — N structurally-identical contractions that differ only in
  γ (which group-by attr the incoming message carries) and σ masks.
  ``PlanCache.run_sparse_batch`` stacks such siblings into ONE jitted call:
  members are grouped by :func:`absorb_batch_key` (root relation, incoming
  attr pattern with off-bag γ attrs canonicalized to positional
  placeholders, σ arity, out-attr pattern), γ-carried dims are padded to the
  group max with the ring's ⊕-identity (0̄ is ⊗-absorbing, so padding can
  never leak into valid slots), and the single-element plan body is
  ``jax.vmap``-ed over the stacked axis.  Stacking, padding and per-member
  slicing all happen *inside* the traced function, so a whole fan-out costs
  one dispatch instead of one per viz.  Kernel routing is unchanged: the
  vmapped body still lowers f32 SUM/COUNT and tropical rows to
  ``segment_aggregate`` under the same cost gate.
"""

from __future__ import annotations

import dataclasses
import functools
import os
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.kernels import costs as kernel_costs
from repro.kernels.segment_aggregate import ops as seg_ops
from repro.kernels.semiring_contract import ops as sc_ops
from repro.kernels.tropical_contract import ops as tc_ops
from repro.relational.relation import LRU, Predicate

from . import distributed as dist
from . import semiring as sr
from .factor import Factor, contract


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _kernel_cost_max() -> int:
    """Max one-hot-matmul work (N·G·V or G·B·A) routed to Pallas off-TPU.

    Resolution: ``REPRO_PLAN_KERNEL_COST`` env override → the measured
    crossover from the committed ``kernel_costs.json`` roofline profile →
    the historical static default (1<<19)."""
    env = os.environ.get("REPRO_PLAN_KERNEL_COST")
    if env is not None:
        return int(env)
    derived = kernel_costs.derived_plan_kernel_cost()
    return derived if derived is not None else (1 << 19)


def use_plans_default() -> bool:
    """Env-gated default for compiled plans (CI matrix: REPRO_USE_PLANS=0/1
    keeps the legacy un-jitted fallback path covered)."""
    return os.environ.get("REPRO_USE_PLANS", "1").lower() not in ("0", "false")


def batch_fanout_default() -> bool:
    """Env-gated default for batched crossfilter fan-out (REPRO_BATCH_FANOUT);
    benchmarks A/B the batched vs per-viz dispatch path through this knob."""
    return os.environ.get("REPRO_BATCH_FANOUT", "1").lower() not in ("0", "false")


def batch_calibration_default() -> bool:
    """Env-gated default for level-batched calibration passes
    (REPRO_BATCH_CALIBRATION; CI runs a 0/1 matrix axis).  When off — or when
    compiled plans are off — calibration degrades to the per-edge loop."""
    return os.environ.get("REPRO_BATCH_CALIBRATION", "1").lower() not in ("0", "false")


def calibration_union_budget() -> int:
    """Max product of γ domain sizes one union-carry calibration query may
    accumulate (REPRO_CALIBRATION_UNION_BUDGET).  Bounds the widest message a
    shared calibration pass materializes: per-row ⊗ lanes scale with the
    product, so the default keeps the fact-bag working set ~O(512·N·4B) while
    collapsing the most traces (measured knee on the crossfilter suite).

    Resolution mirrors :func:`_kernel_cost_max`: env override → roofline
    profile's derived budget → static 512."""
    env = os.environ.get("REPRO_CALIBRATION_UNION_BUDGET")
    if env is not None:
        return int(env)
    derived = kernel_costs.derived_union_budget()
    return derived if derived is not None else 512


def sparse_batch_elems() -> int:
    """Max rows·width element volume one vmapped sparse-absorption dispatch
    may carry (``REPRO_SPARSE_BATCH_ELEMS``; 0 = unbounded).

    The vmapped absorption's cost grows superlinearly with member count on
    the CPU backend (measured: break-even near width 4 at 5k fact rows,
    3-5x sequential by width 32), so one-dispatch-per-group is only
    profitable while the dispatch volume stays small.  Wider groups split
    into chunks of at least 2 members, keeping cross-session sharing intact
    while the per-dispatch cost stays near the sequential line."""
    env = os.environ.get("REPRO_SPARSE_BATCH_ELEMS")
    if env is not None:
        return int(env)
    return 1 << 18


def fuse_level_default() -> bool:
    """Env-gated default for level-fused kernel launches
    (REPRO_FUSE_LEVEL_KERNEL; CI runs a 0/1 axis).  When on — and plans plus
    level batching are on — each calibration level dispatches ONE jitted call
    whose kernel-eligible messages share a single multi-segment Pallas
    launch."""
    return os.environ.get("REPRO_FUSE_LEVEL_KERNEL", "1").lower() not in ("0", "false")


def expand_rows_field(field: sr.Field, have: Sequence[str], want: Sequence[str],
                      trailing: Sequence[int]) -> sr.Field:
    """Insert size-1 axes so leaves go (N, *have_dims, *t) → (N, *want_dims, *t).

    ``have`` must be a subsequence of ``want``; trailing statistic dims ride
    along unchanged.  Shared by the compiled plans and the legacy sparse path.
    """
    leaves, treedef = jax.tree_util.tree_flatten(field)
    out = []
    for leaf, t in zip(leaves, trailing):
        cur = list(leaf.shape)
        new_shape = [cur[0]]
        hi = 1
        for a in want:
            if a in have:
                new_shape.append(cur[hi])
                hi += 1
            else:
                new_shape.append(1)
        new_shape += cur[hi:]
        out.append(leaf.reshape(new_shape))
    return jax.tree_util.tree_unflatten(treedef, out)


def _field_struct(field: sr.Field) -> tuple:
    return tuple((tuple(l.shape), str(l.dtype)) for l in jax.tree_util.tree_leaves(field))


@dataclasses.dataclass
class PlanStats:
    """Cumulative plan-cache counters (exposed via ``Treant.cache_stats``)."""

    plans_built: int = 0     # structural misses → new trace + compile
    plan_hits: int = 0       # executions served by an existing compiled plan
    kernel_execs: int = 0    # executions that ran a Pallas kernel path
    fallback_execs: int = 0  # executions on the lax/einsum fallback path
    # batched absorption plans (run_sparse_batch)
    batched_execs: int = 0        # vmapped batched calls dispatched
    batched_absorptions: int = 0  # absorptions served by those calls (Σ widths)
    batch_width: int = 0          # widest batch observed (max, not a sum)
    # level-batched calibration (run_message_batch): whole upward/downward
    # levels stacked into vmapped calls, plus how many message
    # materializations calibration dispatched in total (per-edge loop: one
    # per computed message; batched: one per level group)
    level_batched_execs: int = 0     # vmapped level-batch calls dispatched
    level_batched_messages: int = 0  # messages served by those calls (Σ widths)
    level_batch_width: int = 0       # widest level batch observed (max)
    calibration_dispatches: int = 0  # message dispatches issued by calibration
    # level-fused launches (run_level): every kernel-eligible message of a
    # calibration level ⊕-reduced by ONE multi-segment Pallas launch
    fused_level_launches: int = 0    # fused level launches dispatched
    fused_level_messages: int = 0    # messages served by those launches
    # cross-session batched fan-out (TreantServer): vmapped dispatches whose
    # members span >1 session, and the widest distinct-session count observed
    cross_session_execs: int = 0
    cross_session_width: int = 0
    # mesh-sharded execution (PlanCache(mesh=...)): dispatches that ran under
    # shard_map, the bytes their ⊕-all-reduce collectives carried (static per
    # plan: Σ output-factor payloads), and the worst row imbalance observed
    # (max valid rows per shard / ideal per-shard rows)
    shard_execs: int = 0
    allreduce_bytes: int = 0
    shard_imbalance: float = 0.0
    # bin cubes (core/predictive.py): think-time γ∪{dim} materializations
    # built through this engine, and warm brushes served by slicing one
    # (select + ⊕-marginalize — no plan execution, no store probe)
    cube_builds: int = 0
    cube_slices: int = 0

    # counters that are high-water marks, not sums: cross-engine aggregation
    # (Treant.cache_stats) takes max for these and Σ for everything else
    MAX_FIELDS = (
        "batch_width", "level_batch_width", "cross_session_width",
        "shard_imbalance",
    )

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)


@functools.lru_cache(maxsize=512)
def _compiled_slice(dim: str, group_by: tuple[str, ...]):
    """One jitted select∘project per (dim, γ): Factor is a pytree with
    (attrs, ring) static, so jax.jit specializes per cube structure and the
    warm brush costs a single compiled dispatch instead of one eager op per
    σ mask plus the marginalization."""

    def run(cube, masks):
        f = cube
        for m in masks:
            f = f.select(dim, m)
        return f.project_to(group_by)

    return jax.jit(run)


@functools.lru_cache(maxsize=1024)
def _device_mask(data: bytes, shape: tuple[int, ...], dtype: str):
    """Content-addressed device copy of a σ mask: the same predicate fans
    out to every sibling viz, so without this each viz pays its own
    host→device transfer of an identical (tiny) mask."""
    return jnp.asarray(np.frombuffer(data, dtype=dtype).reshape(shape))


def _to_device_masks(masks) -> tuple:
    out = []
    for m in masks:
        arr = np.asarray(m)
        out.append(_device_mask(arr.tobytes(), arr.shape, str(arr.dtype)))
    return tuple(out)


def slice_bin_cube(cube, dim: str, masks, group_by, stats: PlanStats | None = None):
    """Serve a brush from a parked γ∪{dim} bin cube: σ as ``select`` (0̄ is
    the ⊕-identity, so zero-annotating non-matching bins is exact for every
    semiring) then ⊕-marginalize ``dim`` away via ``project_to``.  With no
    masks this serves ``ClearFilter`` (pure marginalization).  O(bins) array
    work — no store probes, no plan executions."""
    fn = _compiled_slice(dim, tuple(group_by))
    f = fn(cube, _to_device_masks(masks))
    if stats is not None:
        stats.cube_slices += 1
    return f


@functools.lru_cache(maxsize=512)
def _compiled_slice_batch(spec: tuple):
    """One jitted call covering a whole fan-out of cube slices: ``spec`` is
    a tuple of (dim, group_by) per viz, the cubes/masks ride in as pytrees.
    A 7-viz crossfilter brush costs ONE compiled dispatch instead of seven —
    the cube analog of ``batch_fanout``'s vmapped absorption groups."""

    def run(cubes, masks_list):
        outs = []
        for (dim, group_by), cube, masks in zip(spec, cubes, masks_list):
            f = cube
            for m in masks:
                f = f.select(dim, m)
            outs.append(f.project_to(group_by))
        return tuple(outs)

    return jax.jit(run)


def slice_bin_cubes(items, stats: PlanStats | None = None) -> list:
    """Batched :func:`slice_bin_cube`: ``items`` is a list of
    (cube_factor, dim, masks, group_by); returns the sliced factors in
    order, produced by a single compiled dispatch."""
    spec = tuple((dim, tuple(gb)) for _, dim, _, gb in items)
    fn = _compiled_slice_batch(spec)
    outs = fn(
        tuple(c for c, _, _, _ in items),
        tuple(_to_device_masks(m) for _, _, m, _ in items),
    )
    if stats is not None:
        stats.cube_slices += len(items)
    return list(outs)


@dataclasses.dataclass(frozen=True)
class _Plan:
    fn: Callable
    uses_kernel: bool
    # level plans only: per-group kernel routing + Σ width of fused groups
    group_kernel: tuple = ()
    fused_messages: int = 0
    # mesh-sharded plans only: the body runs under shard_map and every output
    # factor is ⊕-all-reduced; allreduce_bytes is the static Σ of those
    # collective payloads (one per output factor per dispatch)
    sharded: bool = False
    allreduce_bytes: int = 0


# ---------------------------------------------------------------------------
# sparse-bag plan: gather ⊗ rowwise → σ row mask → segment-⊕ → reshape
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class _SparseMeta:
    """Static facts about one sparse contraction the level plan needs to
    route its rowwise output through the fused kernel."""

    total: int                       # flattened local-out segment count
    carried_dims: tuple[int, ...]    # γ-carried dims of the rowwise output
    use_kernel: bool
    cost: int
    row_bytes: int                   # device bytes the body holds per row


# ---------------------------------------------------------------------------
# row blocks: bound the per-row intermediate of one plan body
# ---------------------------------------------------------------------------

# A sparse body ⊗-expands every row to its carried γ cells before the
# segment-⊕, so one call holds rows × cells × leaves values at once: at a
# fact table of millions of rows and a few hundred carried cells that is
# gigabytes.  A body whose slab would exceed this many bytes is built for one
# equal row block and scanned over the blocks, ⊕-combining their partial
# factors (⊕ is associative and pad rows carry its identity, so any equal
# split of a power-of-two row bucket is exact).
ROW_SLAB_BYTES = 1 << 28
_MIN_BLOCK_ROWS = 2048

# which of a sparse body's (vals, in_fields, in_idx, pred_masks, pred_codes,
# seg_idx) arguments are row-major; the batched layout has the same prefixes
_ROW_MAJOR = (True, False, True, False, True, True)


def _row_blocks(n: int, row_bytes: int) -> int:
    """Power-of-two number of equal row blocks keeping a block's slab within
    :data:`ROW_SLAB_BYTES` (1 = run the rows in one piece)."""
    blocks = 1
    while (
        (n // blocks) * row_bytes > ROW_SLAB_BYTES
        and n // blocks >= 2 * _MIN_BLOCK_ROWS
        and (n // blocks) % 2 == 0
    ):
        blocks *= 2
    return blocks


def _scan_rows(body: Callable, ring: sr.Semiring, blocks: int,
               row_major) -> Callable:
    """Run ``body`` (built for 1/``blocks`` of the rows) over equal row
    blocks with ``lax.scan``, ⊕-combining its Factor outputs.

    ``row_major`` is a pytree prefix of the arguments marking the row-major
    ones; the rest are passed whole to every block."""
    if blocks == 1:
        return body
    is_factor = lambda x: isinstance(x, Factor)  # noqa: E731

    def split(rm, sub):
        if not rm:
            return ()
        return jax.tree_util.tree_map(
            lambda l: l.reshape((blocks, l.shape[0] // blocks) + l.shape[1:]), sub
        )

    def run(*args):
        xs = jax.tree_util.tree_map(split, row_major, args)

        def call(block):
            return body(*jax.tree_util.tree_map(
                lambda rm, sub, b: b if rm else sub, row_major, args, block
            ))

        shapes = jax.eval_shape(call, jax.tree_util.tree_map(lambda l: l[0], xs))
        init = jax.tree_util.tree_map(
            lambda f: Factor(f.attrs, ring.zeros(f.domain_shape), ring),
            shapes, is_leaf=is_factor,
        )

        def step(acc, block):
            out = call(block)
            return jax.tree_util.tree_map(
                lambda a, o: a.add(o), acc, out, is_leaf=is_factor
            ), None

        return jax.lax.scan(step, init, xs)[0]

    return run


def _blocked(build: Callable, n: int, ring: sr.Semiring, row_major):
    """``build(rows) -> (body, row_bytes, info)`` makes a body over ``rows``
    rows; returns ``(body over all n rows, info)``, row-blocked when the
    whole slab would exceed :data:`ROW_SLAB_BYTES`."""
    body, row_bytes, info = build(n)
    blocks = _row_blocks(n, row_bytes)
    if blocks == 1:
        return body, info
    body, _, info = build(n // blocks)
    return _scan_rows(body, ring, blocks, row_major), info


def _sparse_plan_parts(
    ring: sr.Semiring,
    rel_attrs: tuple[str, ...],
    doms: dict[str, int],
    in_attrs_list: tuple[tuple[str, ...], ...],
    pred_attrs: tuple[str, ...],
    out_attrs: tuple[str, ...],
    n: int,
) -> tuple[Callable, Callable, Callable, _SparseMeta]:
    """The raw (un-jitted) single-contraction body shared by the scalar plan
    (jit directly) and the batched plan (pad + stack + vmap, then jit),
    split as (fn, rowwise, finalize, meta) so the level-fused plan can run
    the rowwise stage per message and hand ALL segment reductions of a level
    to one multi-segment kernel launch between rowwise and finalize."""
    rel_set = set(rel_attrs)
    local_out = tuple(a for a in out_attrs if a in rel_set)
    total = int(np.prod([doms[a] for a in local_out])) if local_out else 1

    # static replay of the carried-γ evolution across incoming messages
    steps: list[tuple[tuple, tuple, tuple, tuple, tuple]] = []
    carried: tuple[str, ...] = ()
    for m_attrs in in_attrs_list:
        shared = tuple(a for a in m_attrs if a in rel_set)
        extra = tuple(a for a in m_attrs if a not in rel_set)
        want = carried + tuple(a for a in extra if a not in carried)
        steps.append((m_attrs, shared, extra, carried, want))
        carried = want
    carried_dims = tuple(doms[a] for a in carried)
    carried_out = [a for a in out_attrs if a not in rel_set]
    assert set(carried_out) <= set(carried), (
        f"carried attrs {carried_out} not available (have {list(carried)})"
    )

    op = ring.kernel_segment_op
    vcols = int(np.prod(carried_dims)) if carried_dims else 1
    cost = n * max(total, 1) * vcols * len(ring.trailing)
    use_kernel = (
        op is not None
        and ring.dtype == jnp.float32
        and all(t == 0 for t in ring.trailing)
        and n > 0
        and (_on_tpu() or cost <= _kernel_cost_max())
    )
    out_shape = tuple(doms[a] for a in local_out)

    def rowwise(vals, in_fields, in_idx, pred_masks, pred_codes):
        for (m_attrs, shared, extra, have, want), field, idx in zip(
            steps, in_fields, in_idx
        ):
            mp = Factor(m_attrs, field, ring).project_to(shared + extra)
            dims = [doms[a] for a in shared]

            def gather(leaf):
                lead = leaf.reshape(
                    (int(np.prod(dims)) if shared else 1,) + leaf.shape[len(shared):]
                )
                if shared:
                    return jnp.take(lead, idx, axis=0)
                return jnp.broadcast_to(lead, (n,) + lead.shape[1:])

            leaves, treedef = jax.tree_util.tree_flatten(mp.field)
            g = jax.tree_util.tree_unflatten(treedef, [gather(l) for l in leaves])
            vals = ring.mul(
                expand_rows_field(vals, have, want, ring.trailing),
                expand_rows_field(g, extra, want, ring.trailing),
            )
        if pred_attrs:
            # σ as a rowwise ⊗ with 0̄/1̄: gather each domain mask at the row
            # codes on-device (the mask *content* is a traced arg, so new
            # selections re-execute the same compiled plan)
            rowm = pred_masks[0][pred_codes[0]]
            for mask, codes in zip(pred_masks[1:], pred_codes[1:]):
                rowm = rowm & mask[codes]
            zeros = ring.zeros((n,) + carried_dims)
            leaves, treedef = jax.tree_util.tree_flatten(vals)
            zleaves = jax.tree_util.tree_leaves(zeros)
            out = []
            for leaf, z in zip(leaves, zleaves):
                m = rowm.reshape((n,) + (1,) * (leaf.ndim - 1))
                out.append(jnp.where(m, leaf, z))
            vals = jax.tree_util.tree_unflatten(treedef, out)
        return vals

    def finalize(field):
        field = jax.tree_util.tree_map(
            lambda l: l.reshape(out_shape + l.shape[1:]), field
        )
        return Factor(local_out + carried, field, ring).project_to(out_attrs)

    def fn(vals, in_fields, in_idx, pred_masks, pred_codes, seg_idx):
        vals = rowwise(vals, in_fields, in_idx, pred_masks, pred_codes)
        if use_kernel:
            # compound rings (MOMENTS) stack their equal-shape leaves as
            # extra value columns, so count/sum/sumsq share ONE segment pass
            leaves, treedef = jax.tree_util.tree_flatten(vals)
            slab = jnp.concatenate([l.reshape((n, -1)) for l in leaves], axis=1)
            agg = seg_ops.aggregate_op(seg_idx, slab, total, op=op)
            parts = jnp.split(agg, len(leaves), axis=1) if len(leaves) > 1 else [agg]
            red = [
                p.reshape((total,) + l.shape[1:]) for p, l in zip(parts, leaves)
            ]
            field = jax.tree_util.tree_unflatten(treedef, red)
        else:
            field = ring.segment_reduce(vals, seg_idx, total)
        return finalize(field)

    # the rowwise slab, its σ-masked copy and the kernel's row-major copy
    row_bytes = 3 * vcols * len(ring.trailing) * np.dtype(ring.dtype).itemsize
    meta = _SparseMeta(
        total=total, carried_dims=carried_dims, use_kernel=use_kernel, cost=cost,
        row_bytes=row_bytes,
    )
    return fn, rowwise, finalize, meta


def _sparse_fn(
    ring: sr.Semiring,
    rel_attrs: tuple[str, ...],
    doms: dict[str, int],
    in_attrs_list: tuple[tuple[str, ...], ...],
    pred_attrs: tuple[str, ...],
    out_attrs: tuple[str, ...],
    n: int,
) -> tuple[Callable, _SparseMeta]:
    """The single-contraction body over ``n`` rows, row-blocked if large."""

    def build(rows):
        fn, _, _, meta = _sparse_plan_parts(
            ring, rel_attrs, doms, in_attrs_list, pred_attrs, out_attrs, rows
        )
        return fn, meta.row_bytes, meta

    return _blocked(build, n, ring, _ROW_MAJOR)


def _build_sparse_plan(
    ring: sr.Semiring,
    rel_attrs: tuple[str, ...],
    doms: dict[str, int],
    in_attrs_list: tuple[tuple[str, ...], ...],
    pred_attrs: tuple[str, ...],
    out_attrs: tuple[str, ...],
    n: int,
) -> _Plan:
    fn, meta = _sparse_fn(
        ring, rel_attrs, doms, in_attrs_list, pred_attrs, out_attrs, n
    )
    return _Plan(fn=jax.jit(fn), uses_kernel=meta.use_kernel)


# ---------------------------------------------------------------------------
# mesh-sharded plans: shard_map the body over row blocks, ⊕-all-reduce γ
# ---------------------------------------------------------------------------

def _sparse_shard_specs(axis: str) -> tuple:
    """shard_map in_specs (pytree prefixes) for the (vals, in_fields, in_idx,
    pred_masks, pred_codes, seg_idx) layout every sparse plan body takes:
    row-major arrays (lifts, gather indices, σ row codes, segment ids) shard
    on the mesh axis; γ-indexed message fields and σ domain masks replicate.
    The same prefixes cover the batched (tuple-of-members) layout."""
    return (P(axis), P(), P(axis), P(), P(axis), P(axis))


def _out_factor_bytes(ring: sr.Semiring, doms: dict[str, int],
                      out_attrs: tuple[str, ...]) -> int:
    """Static payload of one ⊕-all-reduced output factor — the (|γ|, V)
    collective size (scalar-leaf approximation for compound rings)."""
    cells = int(np.prod([doms[a] for a in out_attrs])) if out_attrs else 1
    return cells * len(ring.trailing) * np.dtype(ring.dtype).itemsize


def _build_sharded_sparse_plan(
    ring: sr.Semiring,
    rel_attrs: tuple[str, ...],
    doms: dict[str, int],
    in_attrs_list: tuple[tuple[str, ...], ...],
    pred_attrs: tuple[str, ...],
    out_attrs: tuple[str, ...],
    n: int,
    mesh,
    axis: str,
) -> _Plan:
    """Row-sharded single contraction over a 1-D device mesh.

    The local body is the *unchanged* rowwise → σ → segment-⊕ pipeline built
    for a 1/nshards row block (pad rows carry the ⊕-identity, so any block
    split of the padded bucket is exact); the resulting γ-indexed partial
    factor is ⊕-all-reduced before it leaves shard_map.  Every cross-shard
    message is therefore a tiny (|γ|, V) collective — never a join.
    """
    nshards = int(mesh.shape[axis])
    assert n % nshards == 0, f"row bucket {n} not divisible by mesh {nshards}"
    fn_local, meta = _sparse_fn(
        ring, rel_attrs, doms, in_attrs_list, pred_attrs, out_attrs,
        n // nshards,
    )
    collective = dist.ring_collective(ring)
    assert collective is not None, "caller gates on ring_collective"

    def local(vals, in_fields, in_idx, pred_masks, pred_codes, seg_idx):
        fact = fn_local(vals, in_fields, in_idx, pred_masks, pred_codes, seg_idx)
        return dist.allreduce_field(fact, collective, axis)

    sm = jax.shard_map(
        local, mesh=mesh, in_specs=_sparse_shard_specs(axis), out_specs=P(),
        check_vma=False,
    )
    return _Plan(
        fn=jax.jit(sm), uses_kernel=meta.use_kernel, sharded=True,
        allreduce_bytes=_out_factor_bytes(ring, doms, out_attrs),
    )


def _build_sharded_batched_sparse_plan(
    ring: sr.Semiring,
    rel_attrs: tuple[str, ...],
    doms: dict[str, int],
    in_attrs_list: tuple[tuple[str, ...], ...],
    pred_attrs: tuple[str, ...],
    out_attrs: tuple[str, ...],
    n: int,
    member_dims: tuple[dict[str, int], ...],
    mesh,
    axis: str,
) -> _Plan:
    """Row-sharded variant of the vmapped batch plan: B members' rowwise
    stages run per shard (the vmap sits *inside* the local body), then each
    member's sliced output factor is ⊕-all-reduced."""
    nshards = int(mesh.shape[axis])
    assert n % nshards == 0
    bfn, meta = _batched_sparse_fn(
        ring, rel_attrs, doms, in_attrs_list, pred_attrs, out_attrs,
        n // nshards, member_dims,
    )
    collective = dist.ring_collective(ring)

    def local(vals_list, in_fields_list, in_idx, pred_masks_list, pred_codes,
              seg_idx):
        facts = bfn(vals_list, in_fields_list, in_idx, pred_masks_list,
                    pred_codes, seg_idx)
        return dist.allreduce_field(facts, collective, axis)

    sm = jax.shard_map(
        local, mesh=mesh, in_specs=_sparse_shard_specs(axis), out_specs=P(),
        check_vma=False,
    )
    bytes_ = sum(
        _out_factor_bytes(ring, {**doms, **md}, out_attrs)
        for md in member_dims
    )
    return _Plan(fn=jax.jit(sm), uses_kernel=meta.use_kernel, sharded=True,
                 allreduce_bytes=bytes_)


# ---------------------------------------------------------------------------
# batched absorption plans: pad γ dims → stack → vmap, one dispatch per group
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class AbsorbItem:
    """One pending sparse-bag absorption, deferred so siblings can batch.

    ``rel`` is the (single) relation of the absorption bag, ``vals`` its
    per-row lift, ``incoming`` the cached/computed messages from every
    neighbor, ``preds`` the σ placed on this bag, ``out_attrs`` the
    separator-free absorption output (γ restricted to the subtree).
    """

    rel: object                      # relational.Relation
    vals: sr.Field
    incoming: tuple[Factor, ...]
    preds: tuple[Predicate, ...]
    out_attrs: tuple[str, ...]


@dataclasses.dataclass
class _GroupSpec:
    """One canonicalized batch group: members in canonical order plus all
    the statics the batched / level-fused plan builders consume."""

    items: list
    stats: list | None
    in_canon: tuple
    out_canon: tuple
    member_dims: tuple
    doms: dict
    pred_attrs: tuple
    inverse: dict          # canonical position → caller position
    key: tuple             # version-free trace key


def _canon_absorption(item: AbsorbItem) -> tuple[tuple, tuple, dict[str, str]]:
    """Canonicalize off-bag (γ-carried) attrs to positional placeholders.

    Two absorptions batch iff they differ only in *which* off-bag attr each
    structural slot carries (and its domain size) — e.g. sibling vizzes
    grouping by ``airport_state`` vs ``month``.  Placeholders are assigned in
    first-appearance order scanning incoming messages then out_attrs, so the
    coincidence pattern (one attr appearing in several slots) is preserved.
    """
    rel_set = set(item.rel.attrs)
    ph: dict[str, str] = {}

    def c(a: str) -> str:
        if a in rel_set:
            return a
        if a not in ph:
            ph[a] = f"·{len(ph)}"
        return ph[a]

    in_canon = tuple(tuple(c(a) for a in m.attrs) for m in item.incoming)
    out_canon = tuple(c(a) for a in item.out_attrs)
    return in_canon, out_canon, ph


def absorb_batch_key(ring: sr.Semiring, item: AbsorbItem) -> tuple:
    """Grouping key for batchable absorptions (the *batch signature*).

    Everything the shared (in_axes=None) plan inputs depend on must be here:
    the relation version (row codes → in_idx/pred_codes/seg_idx), the rel
    attr order and domains, σ attrs, the canonical incoming/out patterns and
    the lift's field structure.  Placeholder domain sizes are deliberately
    absent — they are padded per group and only key the *trace*.
    """
    in_canon, out_canon, _ = _canon_absorption(item)
    rel = item.rel
    return (
        "sparse_batch", ring.name, rel.key, rel.attrs,
        tuple(rel.domains[a] for a in rel.attrs), rel.num_rows,
        in_canon, tuple(p.attr for p in item.preds), out_canon,
        _field_struct(item.vals),
    )


def _pad_value(zero_leaf) -> float | bool:
    """The constant ⊕-identity fill for one field leaf (identity fields are
    constant-valued in every ring here: 0.0, ±inf, False)."""
    flat = np.asarray(zero_leaf).reshape(-1)
    return flat[0].item() if flat.size else 0.0


def _make_batch_stager(
    ring: sr.Semiring,
    rel_set: set[str],
    doms: dict[str, int],
    in_attrs_list: tuple[tuple[str, ...], ...],
    pred_attrs: tuple[str, ...],
) -> Callable:
    """Traced-side stacking of B members' inputs: γ-carried message dims pad
    to the group max with the ⊕-identity (0̄ is ⊗-absorbing, so padding can
    never leak into valid slots), then everything stacks on a new lead axis."""
    pad_vals = [_pad_value(z) for z in jax.tree_util.tree_leaves(ring.zeros(()))]

    def _stack(fields):
        return jax.tree_util.tree_map(lambda *ls: jnp.stack(ls), *fields)

    def _pad_message(j: int, field: sr.Field) -> sr.Field:
        m_attrs = in_attrs_list[j]
        leaves, treedef = jax.tree_util.tree_flatten(field)
        out = []
        for leaf, t, pv in zip(leaves, ring.trailing, pad_vals):
            pads = [
                (0, (doms[a] - leaf.shape[k]) if a not in rel_set else 0)
                for k, a in enumerate(m_attrs)
            ] + [(0, 0)] * t
            out.append(jnp.pad(leaf, pads, constant_values=pv)
                       if any(p[1] for p in pads) else leaf)
        return jax.tree_util.tree_unflatten(treedef, out)

    def stage(vals_list, in_fields_list, pred_masks_list):
        vals = _stack(vals_list)
        in_fields = tuple(
            _stack([_pad_message(j, member[j]) for member in in_fields_list])
            for j in range(len(in_attrs_list))
        )
        pred_masks = tuple(
            jnp.stack([pm[k] for pm in pred_masks_list])
            for k in range(len(pred_attrs))
        )
        return vals, in_fields, pred_masks

    return stage


def _slice_member(
    ring: sr.Semiring,
    fact: Factor,
    dims: dict[str, int],
    doms: dict[str, int],
    lead: int | None = None,
) -> Factor:
    """Slice one member's valid region out of a padded (optionally stacked)
    factor: placeholder dims shrink back to the member's actual sizes."""
    leaves, treedef = jax.tree_util.tree_flatten(fact.field)
    sliced = []
    for leaf, t in zip(leaves, ring.trailing):
        idx = tuple(
            ([] if lead is None else [lead])
            + [slice(0, dims.get(a, doms[a])) for a in fact.attrs]
            + [slice(None)] * t
        )
        sliced.append(leaf[idx])
    return Factor(fact.attrs, jax.tree_util.tree_unflatten(treedef, sliced), ring)


def _batched_sparse_fn(
    ring: sr.Semiring,
    rel_attrs: tuple[str, ...],
    doms: dict[str, int],
    in_attrs_list: tuple[tuple[str, ...], ...],
    pred_attrs: tuple[str, ...],
    out_attrs: tuple[str, ...],
    n: int,
    member_dims: tuple[dict[str, int], ...],
) -> tuple[Callable, _SparseMeta]:
    """The raw (un-jitted) B-member batch body: pad + stack + vmap the
    single-contraction fn, then slice members back out (row-blocked if
    the B members' slabs together are large)."""
    nmembers = len(member_dims)
    stage = _make_batch_stager(ring, set(rel_attrs), doms, in_attrs_list, pred_attrs)

    def build(rows):
        fn, _, _, meta = _sparse_plan_parts(
            ring, rel_attrs, doms, in_attrs_list, pred_attrs, out_attrs, rows
        )

        def bfn(vals_list, in_fields_list, in_idx, pred_masks_list, pred_codes,
                seg_idx):
            vals, in_fields, pred_masks = stage(
                vals_list, in_fields_list, pred_masks_list
            )
            batched = jax.vmap(fn, in_axes=(0, 0, None, 0, None, None))(
                vals, in_fields, in_idx, pred_masks, pred_codes, seg_idx
            )
            # slice each member's valid region back out of the padded stack
            return tuple(
                _slice_member(ring, batched, member_dims[i], doms, lead=i)
                for i in range(nmembers)
            )

        return bfn, nmembers * meta.row_bytes, meta

    return _blocked(build, n, ring, _ROW_MAJOR)


def _build_batched_sparse_plan(
    ring: sr.Semiring,
    rel_attrs: tuple[str, ...],
    doms: dict[str, int],
    in_attrs_list: tuple[tuple[str, ...], ...],
    pred_attrs: tuple[str, ...],
    out_attrs: tuple[str, ...],
    n: int,
    member_dims: tuple[dict[str, int], ...],
) -> _Plan:
    """Compile B structurally-identical absorptions as ONE jitted call.

    ``in_attrs_list``/``out_attrs`` use canonical placeholder names; ``doms``
    maps placeholders to the *padded* (group-max) sizes; ``member_dims[i]``
    maps placeholders to member i's actual sizes.  Padding, stacking and the
    per-member output slicing all live inside the traced function, so the
    host dispatches exactly one executable per batch — the whole point.
    """
    bfn, meta = _batched_sparse_fn(
        ring, rel_attrs, doms, in_attrs_list, pred_attrs, out_attrs, n, member_dims
    )
    return _Plan(fn=jax.jit(bfn), uses_kernel=meta.use_kernel)


# ---------------------------------------------------------------------------
# level-fused plan: EVERY group of a calibration level in one jitted call,
# kernel-eligible groups sharing a single multi-segment Pallas launch
# ---------------------------------------------------------------------------

def _level_plan_parts(ring: sr.Semiring, group_statics: tuple) -> tuple:
    """The raw (un-jitted) level body as ``(lfn, group_kernel,
    fused_messages)`` — split from :func:`_build_level_plan` so the sharded
    variant can wrap ``lfn`` in shard_map before jitting.

    ``group_statics[g]`` is ``(rel_attrs, doms, in_canon, pred_attrs,
    out_canon, n, member_dims)`` exactly as :func:`_build_batched_sparse_plan`
    takes them (canonical placeholders, padded doms).  Per group the rowwise
    stage (gather ⊗ σ) runs as before — vmapped when the group has several
    members — but instead of one ``aggregate_op`` per member, every
    kernel-eligible member across ALL groups contributes its
    ``(seg_idx, value slab, num_segments)`` descriptor to a single
    ``level_aggregate`` launch; groups that fail the kernel gate ⊕-reduce on
    the lax path *inside the same trace*, and so does every group when the
    fused launch's slab would exceed :data:`ROW_SLAB_BYTES` (each then runs
    its own row-blocked body).  Either way the host dispatches one
    executable per level, which is the whole point: offline calibration goes
    from one dispatch per batch group to ≤ tree-depth launches.
    """
    parts = []
    for (rel_attrs, doms, in_canon, pred_attrs, out_canon, n, member_dims) in (
        group_statics
    ):
        _, rowwise, finalize, meta = _sparse_plan_parts(
            ring, rel_attrs, doms, in_canon, pred_attrs, out_canon, n
        )
        nmembers = len(member_dims)
        parts.append({
            "rowwise": rowwise, "finalize": finalize, "meta": meta,
            "stage": (
                _make_batch_stager(ring, set(rel_attrs), doms, in_canon, pred_attrs)
                if nmembers > 1 else None
            ),
            "statics": (ring, rel_attrs, doms, in_canon, pred_attrs, out_canon, n),
            "doms": doms, "member_dims": member_dims, "n": n,
        })
    # the fused launch concatenates every member's slab, padded to the
    # widest: when that exceeds the row-slab budget, each group runs its own
    # (row-blocked) body inside this same dispatch instead
    itemsize = np.dtype(ring.dtype).itemsize
    nleaves = len(ring.trailing)
    kernel_parts = [p for p in parts if p["meta"].use_kernel]
    v_max = max(
        (int(np.prod(p["meta"].carried_dims)) * nleaves for p in kernel_parts),
        default=0,
    )
    fuse = sum(
        len(p["member_dims"]) * p["n"] * (p["meta"].row_bytes + 2 * v_max * itemsize)
        for p in kernel_parts
    ) <= ROW_SLAB_BYTES
    for p in parts:
        p["fused"] = fuse and p["meta"].use_kernel
        if p["fused"]:
            continue
        if len(p["member_dims"]) == 1:
            own, _ = _sparse_fn(*p["statics"])
            p["own"] = lambda v, f, i, m, c, s, own=own: (
                own(v[0], f[0], i, m[0], c, s),
            )
        else:
            p["own"], _ = _batched_sparse_fn(*p["statics"], p["member_dims"])
    group_kernel = tuple(p["meta"].use_kernel for p in parts)
    fused_messages = sum(len(p["member_dims"]) for p in parts if p["fused"])
    op = ring.kernel_segment_op

    def lfn(groups_args):
        fused_items: list = []
        fused_slots: list = []
        treedefs: dict = {}
        results: list = [None] * len(parts)
        for g, (part, args) in enumerate(zip(parts, groups_args)):
            if not part["fused"]:
                results[g] = part["own"](*args)
                continue
            vals_list, in_fields_list, in_idx, pred_masks_list, pred_codes, seg_idx = args
            nmembers = len(part["member_dims"])
            if nmembers == 1:
                member_rvs = [part["rowwise"](
                    vals_list[0], in_fields_list[0], in_idx,
                    pred_masks_list[0], pred_codes,
                )]
            else:
                vals, in_fields, pred_masks = part["stage"](
                    vals_list, in_fields_list, pred_masks_list
                )
                rvb = jax.vmap(part["rowwise"], in_axes=(0, 0, None, 0, None))(
                    vals, in_fields, in_idx, pred_masks, pred_codes
                )
                member_rvs = [
                    jax.tree_util.tree_map(lambda l, b=b: l[b], rvb)
                    for b in range(nmembers)
                ]
            n = part["n"]
            for b, rv in enumerate(member_rvs):
                leaves, treedef = jax.tree_util.tree_flatten(rv)
                treedefs[g] = treedef
                slab = jnp.concatenate(
                    [l.reshape((n, -1)) for l in leaves], axis=1
                )
                fused_items.append((seg_idx, slab, part["meta"].total))
                fused_slots.append((g, b))
        if fused_items:
            fused_outs = seg_ops.level_aggregate(fused_items, op=op)
            fused_facts: dict = {}
            for (g, b), agg in zip(fused_slots, fused_outs):
                part = parts[g]
                total = part["meta"].total
                carried_dims = part["meta"].carried_dims
                leaf_parts = (
                    jnp.split(agg, nleaves, axis=1) if nleaves > 1 else [agg]
                )
                red = [p.reshape((total,) + carried_dims) for p in leaf_parts]
                field = jax.tree_util.tree_unflatten(treedefs[g], red)
                fact = part["finalize"](field)
                fact = _slice_member(
                    ring, fact, part["member_dims"][b], part["doms"]
                )
                fused_facts.setdefault(g, []).append(fact)
            for g, facts in fused_facts.items():
                results[g] = tuple(facts)
        return tuple(results)

    return lfn, group_kernel, fused_messages


def _build_level_plan(ring: sr.Semiring, group_statics: tuple) -> _Plan:
    lfn, group_kernel, fused_messages = _level_plan_parts(ring, group_statics)
    return _Plan(
        fn=jax.jit(lfn),
        uses_kernel=any(group_kernel),
        group_kernel=group_kernel,
        fused_messages=fused_messages,
    )


def _build_sharded_level_plan(
    ring: sr.Semiring, group_statics: tuple, mesh, axis: str,
) -> _Plan:
    """One fused level dispatch per mesh — the level stays the unit of
    collective scheduling.

    The whole level body (every group's rowwise stage plus the shared
    multi-segment kernel launch) runs per shard on local row blocks; then
    every member factor of every group is ⊕-all-reduced in one pass, so a
    level costs one shard_map dispatch and one collective round regardless
    of how many messages it carries.
    """
    nshards = int(mesh.shape[axis])
    local_statics = tuple(
        (rel_attrs, doms, in_canon, pred_attrs, out_canon, n // nshards,
         member_dims)
        for (rel_attrs, doms, in_canon, pred_attrs, out_canon, n, member_dims)
        in group_statics
    )
    lfn, group_kernel, fused_messages = _level_plan_parts(ring, local_statics)
    collective = dist.ring_collective(ring)
    assert collective is not None, "caller gates on ring_collective"

    def local(groups_args):
        return dist.allreduce_field(lfn(groups_args), collective, axis)

    per_group = _sparse_shard_specs(axis)
    sm = jax.shard_map(
        local, mesh=mesh,
        in_specs=(tuple(per_group for _ in group_statics),),
        out_specs=P(), check_vma=False,
    )
    bytes_ = sum(
        _out_factor_bytes(ring, {**doms, **md}, out_canon)
        for (_ra, doms, _ic, _pa, out_canon, _n, member_dims) in group_statics
        for md in member_dims
    )
    return _Plan(
        fn=jax.jit(sm),
        uses_kernel=any(group_kernel),
        group_kernel=group_kernel,
        fused_messages=fused_messages,
        sharded=True,
        allreduce_bytes=bytes_,
    )


# ---------------------------------------------------------------------------
# dense-bag plan: σ selects → contract (Pallas matmul / einsum / generic)
# ---------------------------------------------------------------------------

def _matmul_split(structs, out: tuple[str, ...]):
    """Decompose a 2-factor contraction as (free1, contracted) × (contracted,
    free2) if no shared attr survives to the output (no batch dims)."""
    (a1, d1), (a2, d2) = structs
    doms = {**dict(zip(a1, d1)), **dict(zip(a2, d2))}
    shared = tuple(a for a in a1 if a in set(a2))
    out_set = set(out)
    if not shared or (out_set & set(shared)):
        return None
    free1 = tuple(a for a in a1 if a in out_set)
    free2 = tuple(a for a in a2 if a in out_set)
    cost = int(
        np.prod([doms[a] for a in free1] or [1])
        * np.prod([doms[a] for a in shared])
        * np.prod([doms[a] for a in free2] or [1])
    )
    return shared, free1, free2, doms, cost


def _build_dense_plan(
    ring: sr.Semiring,
    structs: tuple[tuple[tuple[str, ...], tuple[int, ...]], ...],
    pred_spec: tuple[tuple[str, int], ...],
    out_attrs: tuple[str, ...],
) -> _Plan:
    avail = {a for attrs, _ in structs for a in attrs}
    out = tuple(a for a in out_attrs if a in avail)
    split = None
    # tropical MIN/MAX shares the matmul decomposition: its ⊗ is +, so the
    # (free1, shared) × (shared, free2) split maps 1:1 onto tropical_contract
    tropical = ring.kernel_segment_op in ("min", "max")
    if (
        (ring.is_arithmetic or tropical)
        and len(ring.trailing) == 1
        and ring.dtype == jnp.float32
        and len(structs) == 2
    ):
        cand = _matmul_split(structs, out)
        if cand is not None and (_on_tpu() or cand[4] <= _kernel_cost_max()):
            split = cand

    def fn(fields, pred_masks):
        factors = [Factor(attrs, f, ring) for (attrs, _), f in zip(structs, fields)]
        for (attr, fidx), mask in zip(pred_spec, pred_masks):
            factors[fidx] = factors[fidx].select(attr, mask)
        if split is not None:
            shared, free1, free2, doms, _ = split
            g1 = factors[0].project_to(free1 + shared)
            g2 = factors[1].project_to(shared + free2)
            f1sz = int(np.prod([doms[a] for a in free1])) if free1 else 1
            f2sz = int(np.prod([doms[a] for a in free2])) if free2 else 1
            csz = int(np.prod([doms[a] for a in shared]))
            if tropical:
                o = tc_ops.contract_op(
                    g1.field.reshape((f1sz, csz)),
                    g2.field.reshape((csz, f2sz)),
                    is_min=ring.kernel_segment_op == "min",
                )
            else:
                o = sc_ops.contract_op(
                    g1.field.reshape((f1sz, csz)),
                    g2.field.reshape((csz, f2sz)),
                    None,
                )
            field = o.reshape(
                tuple(doms[a] for a in free1) + tuple(doms[a] for a in free2)
            )
            return Factor(free1 + free2, field, ring).project_to(out)
        return contract(factors, out, ring)

    return _Plan(fn=jax.jit(fn), uses_kernel=split is not None)


# ---------------------------------------------------------------------------
# the cache
# ---------------------------------------------------------------------------

class PlanCache:
    """Compiled-executable cache for bag contractions (one per engine/ring).

    Holds four LRU-bounded device-resident caches: compiled plans, per-row
    lifts, densified base factors, and predicate domain masks.  All keys are
    content-addressed by (relation, version, …) or predicate digest, so no
    invalidation is ever needed — updates allocate new slots and old versions
    age out.
    """

    def __init__(
        self,
        ring: sr.Semiring,
        plan_capacity: int = 256,
        lift_capacity: int = 128,
        factor_capacity: int = 128,
        mask_capacity: int = 512,
        mesh=None,
        mesh_axis: str = dist.SHARD_AXIS,
    ):
        self.ring = ring
        self._plans = LRU(plan_capacity)
        self._lifts = LRU(lift_capacity)
        self._factors = LRU(factor_capacity)
        self._masks = LRU(mask_capacity)
        self.stats = PlanStats()
        # mesh-sharded execution: with a mesh attached and a ⊕-collective for
        # the ring, sparse/batched/level plans row-shard their bodies under
        # shard_map and ⊕-all-reduce the γ-indexed partials.  Rings without a
        # collective (BOOL: ⊕ = ∨) keep the unsharded plans.  Row buckets are
        # powers of two ≥ 64, so they split over any power-of-two mesh; any
        # other split raises where the codes are placed (Catalog).
        self.mesh = mesh
        self.mesh_axis = mesh_axis
        self.shards = int(mesh.shape[mesh_axis]) if mesh is not None else 1
        self._collective = (
            dist.ring_collective(ring) if self.shards > 1 else None
        )

    # -- device-resident input caches ---------------------------------------
    def mask_dev(self, pred: Predicate) -> jax.Array:
        m = self._masks.get(pred.digest)
        if m is None:
            m = jnp.asarray(pred.mask)
            self._masks.put(pred.digest, m)
        return m

    def lift_cached(self, key: tuple, compute: Callable[[], sr.Field]) -> sr.Field:
        v = self._lifts.get(key)
        if v is None:
            v = compute()
            self._lifts.put(key, v)
        return v

    def factor_cached(self, key: tuple, compute: Callable[[], Factor]) -> Factor:
        v = self._factors.get(key)
        if v is None:
            v = compute()
            self._factors.put(key, v)
        return v

    # -- plan execution ------------------------------------------------------
    def _account(self, entry: _Plan, traced: bool, stats) -> None:
        if traced:
            self.stats.plans_built += 1
        else:
            self.stats.plan_hits += 1
        if entry.uses_kernel:
            self.stats.kernel_execs += 1
        else:
            self.stats.fallback_execs += 1
        if stats is not None:
            stats.plan_traces += int(traced)
            stats.plan_hits += int(not traced)
            stats.kernel_execs += int(entry.uses_kernel)

    @property
    def plan_shards(self) -> int:
        """Mesh width the ring's plans shard over (1 = unsharded)."""
        return 1 if self._collective is None else self.shards

    def _account_sharded(self, entry: _Plan, rels) -> None:
        self.stats.shard_execs += 1
        self.stats.allreduce_bytes += entry.allreduce_bytes
        for rel in rels:
            self.stats.shard_imbalance = max(
                self.stats.shard_imbalance,
                dist.shard_imbalance(rel.num_rows, rel.row_bucket, self.shards),
            )

    def sparse_key(
        self, rel, vals: sr.Field, incoming: Sequence[Factor],
        preds: Sequence[Predicate], out_attrs: Sequence[str],
    ) -> tuple:
        return (
            "sparse",
            self.ring.name,
            rel.attrs,
            tuple(rel.domains[a] for a in rel.attrs),
            rel.row_bucket,
            tuple((m.attrs, m.domain_shape) for m in incoming),
            tuple(p.attr for p in preds),
            tuple(out_attrs),
            _field_struct(vals),
        )

    def run_sparse(
        self,
        catalog,
        rel,
        vals: sr.Field,
        incoming: Sequence[Factor],
        preds: Sequence[Predicate],
        out_attrs: tuple[str, ...],
        stats=None,
    ) -> Factor:
        shards = self.plan_shards
        key = self.sparse_key(rel, vals, incoming, preds, out_attrs)
        if shards > 1:
            key = key + (("shards", shards),)
        entry = self._plans.get(key)
        traced = entry is None
        if traced:
            doms = dict(rel.domains)
            for m in incoming:
                doms.update(m.domains)
            build_args = (
                self.ring, rel.attrs, doms, tuple(m.attrs for m in incoming),
                tuple(p.attr for p in preds), tuple(out_attrs), rel.row_bucket,
            )
            entry = (
                _build_sharded_sparse_plan(*build_args, self.mesh, self.mesh_axis)
                if shards > 1 else _build_sparse_plan(*build_args)
            )
            self._plans.put(key, entry)
        rel_set = set(rel.attrs)
        in_fields, in_idx = [], []
        for m in incoming:
            shared = tuple(a for a in m.attrs if a in rel_set)
            in_fields.append(m.field)
            in_idx.append(catalog.dev_flat_codes(rel, shared)[0] if shared else None)
        pred_masks = tuple(self.mask_dev(p) for p in preds)
        pred_codes = tuple(catalog.dev_flat_codes(rel, (p.attr,))[0] for p in preds)
        local_out = tuple(a for a in out_attrs if a in rel_set)
        seg_idx, _ = catalog.dev_flat_codes(rel, local_out)
        out = entry.fn(
            vals, tuple(in_fields), tuple(in_idx), pred_masks, pred_codes, seg_idx
        )
        self._account(entry, traced, stats)
        if entry.sharded:
            self._account_sharded(entry, (rel,))
        return out

    def run_sparse_batch(
        self,
        catalog,
        items: Sequence[AbsorbItem],
        stats_list: Sequence | None = None,
    ) -> list[Factor]:
        """Execute a group of batch-compatible absorptions as one vmapped call.

        Every item must share the same :func:`absorb_batch_key` (the caller
        groups); members differ only in γ-carried attrs/domains, σ mask
        contents and incoming-factor values.  Returns per-member factors
        bit-compatible with ``run_sparse`` on integer-exact data (padding is
        the ⊕-identity, which ⊗ absorbs and ⊕ ignores).
        """
        return self._run_batch(catalog, items, stats_list, calibration=False)

    def run_message_batch(
        self,
        catalog,
        items: Sequence[AbsorbItem],
        stats_list: Sequence | None = None,
    ) -> list[Factor]:
        """Execute one calibration *level*'s batch-compatible messages as one
        vmapped call.

        A message Y(u→v) is the same bag contraction as an absorption with
        ``out_attrs = separator ∪ γ-carry``, so the whole ⊕-identity padding /
        placeholder-canonicalization machinery of :meth:`run_sparse_batch` is
        reused verbatim — only the accounting differs (``level_batched_*``
        counters instead of ``batched_*``).
        """
        return self._run_batch(catalog, items, stats_list, calibration=True)

    def run_level(
        self,
        catalog,
        item_groups: Sequence[Sequence[AbsorbItem]],
        stats_groups: Sequence[Sequence] | None = None,
    ) -> list[list[Factor]]:
        """Execute ALL of one calibration level's batch groups as ONE call.

        ``item_groups`` are the :func:`absorb_batch_key` groups of a level —
        already independent by construction (PAPER.md §4: same-level messages
        never read each other).  The compiled level plan runs every group's
        rowwise stage, fuses all kernel-eligible segment reductions into a
        single multi-segment Pallas launch (``level_aggregate``) and reduces
        the rest on the lax path inside the same trace, so the host issues
        exactly one dispatch per level instead of one per group.  Returns the
        per-group factor lists in the caller's group and member order.
        """
        specs = [
            self._group_spec(items, stats_groups[i] if stats_groups else None)
            for i, items in enumerate(item_groups)
        ]
        # canonical group order: a level's groups arrive in edge-iteration
        # order, which σ-variants can permute without changing structure —
        # sort by trace key so every permutation re-hits the same plan
        order = sorted(range(len(specs)), key=lambda i: repr(specs[i].key))
        # one collective schedule per level, no mixed dispatch
        shards = self.plan_shards
        key = ("level", self.ring.name, tuple(specs[i].key for i in order))
        if shards > 1:
            key = key + (("shards", shards),)
        entry = self._plans.get(key)
        traced = entry is None
        if traced:
            statics = tuple(
                (
                    specs[i].items[0].rel.attrs, specs[i].doms,
                    specs[i].in_canon, specs[i].pred_attrs, specs[i].out_canon,
                    specs[i].items[0].rel.row_bucket, specs[i].member_dims,
                )
                for i in order
            )
            entry = (
                _build_sharded_level_plan(
                    self.ring, statics, self.mesh, self.mesh_axis
                )
                if shards > 1 else _build_level_plan(self.ring, statics)
            )
            self._plans.put(key, entry)
        outs = entry.fn(
            tuple(self._group_args(catalog, specs[i]) for i in order)
        )
        if entry.fused_messages:
            self.stats.fused_level_launches += 1
            self.stats.fused_level_messages += entry.fused_messages
        if entry.sharded:
            self._account_sharded(entry, (s.items[0].rel for s in specs))
        results: list[list[Factor] | None] = [None] * len(specs)
        for pos, i in enumerate(order):
            spec = specs[i]
            width = len(spec.items)
            group_uses_kernel = entry.group_kernel[pos]
            if width > 1:
                # a vmapped group inside the fused launch is still a level
                # batch — keep the level_batched_* counters meaningful
                self.stats.level_batched_execs += 1
                self.stats.level_batched_messages += width
                self.stats.level_batch_width = max(
                    self.stats.level_batch_width, width
                )
            group_results = []
            for it, f, stats in zip(
                spec.items, outs[pos], spec.stats or [None] * width
            ):
                # rename canonical placeholders back to the member's attrs
                group_results.append(Factor(it.out_attrs, f.field, self.ring))
                if traced:
                    self.stats.plans_built += 1
                else:
                    self.stats.plan_hits += 1
                if group_uses_kernel:
                    self.stats.kernel_execs += 1
                else:
                    self.stats.fallback_execs += 1
                if stats is not None:
                    stats.plan_traces += int(traced)
                    stats.plan_hits += int(not traced)
                    stats.kernel_execs += int(group_uses_kernel)
                    if width > 1:
                        stats.level_batched_execs += 1
                        stats.level_batch_width = max(
                            stats.level_batch_width, width
                        )
                traced = False  # one trace per level call, not per member
            # undo the member sort: caller expects its own member order
            results[i] = [
                group_results[spec.inverse[o]] for o in range(width)
            ]
        return results  # type: ignore[return-value]

    def _group_spec(
        self,
        items: Sequence[AbsorbItem],
        stats_list: Sequence | None,
    ) -> "_GroupSpec":
        """Canonicalize one batch group: sorted member order, placeholder
        dims padded to the group max, and the version-free trace key shared
        by the batched and level-fused plans."""
        rel = items[0].rel
        canons = [_canon_absorption(it) for it in items]
        in_canon, out_canon, _ = canons[0]
        member_dims = []
        for it, (_, _, ph) in zip(items, canons):
            adoms: dict[str, int] = {}
            for m in it.incoming:
                adoms.update(m.domains)
            member_dims.append({p: adoms[a] for a, p in ph.items()})
        # canonical member order (by γ-dim signature): the trace key bakes in
        # the per-member dims positionally, so without sorting every
        # permutation of the same sibling set (e.g. when prefetch hits carve
        # different subsets out of a fan-out) would retrace + recompile
        order = sorted(
            range(len(items)), key=lambda i: tuple(sorted(member_dims[i].items()))
        )
        items = [items[o] for o in order]
        member_dims = tuple(member_dims[o] for o in order)
        if stats_list is not None:
            stats_list = [stats_list[o] for o in order]
        inverse = {o: i for i, o in enumerate(order)}
        padded = {
            p: max(md[p] for md in member_dims) for p in (member_dims[0] or {})
        }
        doms = dict(rel.domains)
        doms.update(padded)
        pred_attrs = tuple(p.attr for p in items[0].preds)
        # trace key: like the grouping key, but version-free (codes/masks/
        # fields are runtime args — only shapes matter to the trace) and with
        # the row axis bucketed, so streamed ticks re-hit the compiled plan
        # instead of retracing per version bump
        key = (
            "sparse_batch", self.ring.name, rel.attrs,
            tuple(rel.domains[a] for a in rel.attrs), rel.row_bucket,
            in_canon, pred_attrs, out_canon, _field_struct(items[0].vals),
            tuple(tuple(sorted(md.items())) for md in member_dims),
        )
        return _GroupSpec(
            items=items, stats=stats_list, in_canon=in_canon,
            out_canon=out_canon, member_dims=member_dims, doms=doms,
            pred_attrs=pred_attrs, inverse=inverse, key=key,
        )

    def _group_args(self, catalog, spec: "_GroupSpec") -> tuple:
        """Device-resident runtime inputs for one group, in the (vals_list,
        in_fields_list, in_idx, pred_masks_list, pred_codes, seg_idx) layout
        both the batched and the level-fused plan bodies take."""
        items = spec.items
        rel = items[0].rel
        rel_set = set(rel.attrs)
        in_idx = tuple(
            catalog.dev_flat_codes(rel, tuple(a for a in m.attrs if a in rel_set))[0]
            if any(a in rel_set for a in m.attrs) else None
            for m in items[0].incoming
        )
        pred_codes = tuple(
            catalog.dev_flat_codes(rel, (p.attr,))[0] for p in items[0].preds
        )
        local_out = tuple(a for a in items[0].out_attrs if a in rel_set)
        seg_idx, _ = catalog.dev_flat_codes(rel, local_out)
        return (
            tuple(it.vals for it in items),
            tuple(tuple(m.field for m in it.incoming) for it in items),
            in_idx,
            tuple(tuple(self.mask_dev(p) for p in it.preds) for it in items),
            pred_codes,
            seg_idx,
        )

    def _run_batch(
        self,
        catalog,
        items: Sequence[AbsorbItem],
        stats_list: Sequence | None,
        calibration: bool,
    ) -> list[Factor]:
        assert len(items) >= 2, "batch of one: use run_sparse"
        spec = self._group_spec(items, stats_list)
        items, stats_list, inverse = spec.items, spec.stats, spec.inverse
        rel = items[0].rel
        shards = self.plan_shards
        key = spec.key + (("shards", shards),) if shards > 1 else spec.key
        entry = self._plans.get(key)
        traced = entry is None
        if traced:
            build_args = (
                self.ring, rel.attrs, spec.doms, spec.in_canon, spec.pred_attrs,
                spec.out_canon, rel.row_bucket, spec.member_dims,
            )
            entry = (
                _build_sharded_batched_sparse_plan(
                    *build_args, self.mesh, self.mesh_axis
                )
                if shards > 1 else _build_batched_sparse_plan(*build_args)
            )
            self._plans.put(key, entry)
        outs = entry.fn(*self._group_args(catalog, spec))
        if entry.sharded:
            self._account_sharded(entry, (rel,))
        width = len(items)
        if calibration:
            self.stats.level_batched_execs += 1
            self.stats.level_batched_messages += width
            self.stats.level_batch_width = max(self.stats.level_batch_width, width)
        else:
            self.stats.batched_execs += 1
            self.stats.batched_absorptions += width
            self.stats.batch_width = max(self.stats.batch_width, width)
        results = []
        for it, f, stats in zip(items, outs, stats_list or [None] * width):
            # rename canonical placeholders back to the member's real attrs
            results.append(Factor(it.out_attrs, f.field, self.ring))
            self._account(entry, traced, stats)
            traced = False  # one trace per batched call, not per member
            if stats is not None:
                if calibration:
                    stats.level_batched_execs += 1
                    stats.level_batch_width = max(stats.level_batch_width, width)
                else:
                    stats.batched_absorptions += 1
                    stats.batch_width = max(stats.batch_width, width)
        # undo the canonical sort: caller expects its own member order
        return [results[inverse[o]] for o in range(width)]

    def run_dense(
        self,
        factors: Sequence[Factor],
        preds: Sequence[Predicate],
        out_attrs: tuple[str, ...],
        stats=None,
    ) -> Factor:
        structs = tuple((f.attrs, f.domain_shape) for f in factors)
        avail = {a for f in factors for a in f.attrs}
        pred_spec = []
        for p in preds:
            if p.attr not in avail:  # pragma: no cover — placement guarantees
                raise KeyError(f"σ({p.attr}) not available in bag")
            pred_spec.append(
                (p.attr, next(i for i, f in enumerate(factors) if p.attr in f.attrs))
            )
        pred_spec = tuple(pred_spec)
        key = ("dense", self.ring.name, structs, pred_spec, tuple(out_attrs))
        entry = self._plans.get(key)
        traced = entry is None
        if traced:
            entry = _build_dense_plan(self.ring, structs, pred_spec, tuple(out_attrs))
            self._plans.put(key, entry)
        out = entry.fn(
            tuple(f.field for f in factors), tuple(self.mask_dev(p) for p in preds)
        )
        self._account(entry, traced, stats)
        return out

    def __len__(self):
        return len(self._plans)

    def reset_stats(self):
        self.stats = PlanStats()
