"""Compile-only checks of the main path's kernels for a described TPU v5e.

Nothing here runs on a chip: each test lowers a kernel (or a whole sharded
level plan) for a ``v5e:2x2`` topology described by the installed TPU
compiler and compiles it, which raises whatever Mosaic or XLA would refuse
on the chip — tile shapes that do not match the chip's layouts, vector
shape casts Mosaic cannot lower, programs that do not fit.  Each compiled
program must hold a ``tpu_custom_call``: the Pallas kernel itself, not an
interpreted stand-in.

The topology is described inside a module fixture (never at import), and
the persistent compilation cache is off around the compiles, since entries
compiled for an absent chip cannot be read back.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.core import distributed as dist
from repro.core import plans as plans_mod
from repro.core import semiring as sr
from repro.kernels import mode
from repro.kernels.segment_aggregate import ops as seg_ops
from repro.kernels.semiring_contract import ops as sc_ops
from repro.kernels.tropical_contract import ops as tc_ops

ROWS = 1 << 23   # the row bucket of a 7M-row fact table


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler to describe the chip with
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _no_persistent_cache():
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture
def as_tpu_process(monkeypatch):
    """Steer the plan layer as a TPU process would see it: kernels routed
    without the CPU cost gate and compiled, not interpreted.  Traces made
    that way are dropped afterwards so no later CPU test reuses them."""
    monkeypatch.setattr(plans_mod, "_on_tpu", lambda: True)
    monkeypatch.setattr(mode, "resolve_interpret", lambda interpret: False)
    jax.clear_caches()
    yield
    jax.clear_caches()


def _compile(fn, *shapes):
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("v", [1, 3])
@pytest.mark.parametrize("op", ["sum", "min", "max"])
def test_segment_aggregate_compiles(one_chip, op, v):
    codes = jax.ShapeDtypeStruct((ROWS,), jnp.int32, sharding=one_chip)
    vals = jax.ShapeDtypeStruct((ROWS, v), jnp.float32, sharding=one_chip)
    compiled = _compile(
        lambda c, x: seg_ops.aggregate_op(c, x, 400, op=op, interpret=False),
        codes, vals,
    )
    # no padding of a narrow V to the 128 lanes in HBM (at most to 4 or 8)
    assert compiled.memory_analysis().argument_size_in_bytes <= 2 * ROWS * 4 * (1 + v)


@pytest.mark.parametrize("op", ["sum", "max"])
def test_level_aggregate_compiles(one_chip, op):
    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def fn(c1, x1, c2, x2):
        return seg_ops.level_aggregate([(c1, x1, 365), (c2, x2, 30)], op=op,
                                       interpret=False)

    _compile(fn, sds((ROWS,), jnp.int32), sds((ROWS, 3)),
             sds((1 << 12,), jnp.int32), sds((1 << 12, 1)))


def test_semiring_contract_compiles(one_chip):
    def sds(shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    _compile(lambda m, r, k: sc_ops.contract_op(m, r, k, interpret=False),
             sds((300, 200)), sds((200, 40)), sds((200,)))


def test_tropical_contract_compiles(one_chip):
    def sds(shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    _compile(lambda m, r: tc_ops.contract_op(m, r, is_min=False, interpret=False),
             sds((300, 200)), sds((200, 40)))


def test_sharded_level_plan_compiles(topo, as_tpu_process):
    """One calibration level of two fact-table messages, row-sharded over
    the four chips: per-shard fused kernel launch, then a ⊕-all-reduce."""
    mesh = jax.sharding.Mesh(np.array(topo.devices), (dist.SHARD_AXIS,))
    rows = NamedSharding(mesh, P(dist.SHARD_AXIS))
    whole = NamedSharding(mesh, P())
    doms = {"a": 400, "b": 365}
    # (rel_attrs, doms, in_canon, pred_attrs, out_canon, n, member_dims):
    # a message over b absorbed into the fact bag, out to a — and to b
    statics = (
        (("a", "b"), doms, (("b",),), (), ("a",), ROWS, ({},)),
        (("a", "b"), doms, (("a",),), (), ("b",), ROWS, ({},)),
    )
    plan = plans_mod._build_sharded_level_plan(sr.SUM, statics, mesh, dist.SHARD_AXIS)
    assert plan.sharded and plan.fused_messages == 2

    def group(in_dom):
        return (
            (jax.ShapeDtypeStruct((ROWS,), jnp.float32, sharding=rows),),
            ((jax.ShapeDtypeStruct((in_dom,), jnp.float32, sharding=whole),),),
            (jax.ShapeDtypeStruct((ROWS,), jnp.int32, sharding=rows),),
            ((),),
            (),
            jax.ShapeDtypeStruct((ROWS,), jnp.int32, sharding=rows),
        )

    compiled = _compile(plan.fn, (group(365), group(400)))
    assert "all-reduce" in compiled.as_text()


_SHAPE = re.compile(r"(f32|s32|pred)\[([0-9,]*)\]\{([0-9,]*)(?::([^}]*))?\}")


def _tiled_bytes(dims: str, layout: str, tiling: str | None, itemsize: int) -> tuple:
    """(dense, tiled) bytes of an HLO array shape: the minor dims of its
    layout rounded up to the tile, as the chip stores it in memory."""
    shape = [int(x) for x in dims.split(",")] if dims else []
    tiled = list(shape)
    tile = re.search(r"T\(([0-9,]+)\)", tiling or "")
    if tile and shape:
        minor_to_major = [int(x) for x in layout.split(",")]
        for dim, t in zip(minor_to_major, reversed([int(x) for x in tile.group(1).split(",")])):
            tiled[dim] = -(-tiled[dim] // t) * t
    return int(np.prod(shape)) * itemsize, int(np.prod(tiled)) * itemsize


def _written(hlo: str, scope: str):
    """(shape, op line) of each instruction under ``scope`` that writes an
    array: every instruction outside the bodies of fusions, which keep
    their intermediates in registers."""
    fused = {m.group(1) for m in re.finditer(r" fusion\(.*calls=(%[\w.\-]+)", hlo)}
    comp = None
    for line in hlo.splitlines():
        head = re.match(r"^(?:ENTRY )?(%[\w.\-]+) .*\{$", line.rstrip())
        if head:
            comp = head.group(1)
            continue
        # the scope as named, or vmapped: .../rowwise/... or .../vmap(rowwise)/...
        if comp in fused or not re.search(rf"[/(]{scope}[/)]", line) or " = " not in line:
            continue
        shape = _SHAPE.match(line.split(" = ", 1)[1])
        if shape:
            yield shape, line


def test_taxi_batched_rowwise_is_lane_dense(one_chip, as_tpu_process):
    """The taxi-shaped vmapped absorption (B = 2 over 2^23 rows, messages
    (pu_zone, ·0 = 7), (do_zone,) and (day,), σ on hour) keeps its rowwise
    stage lane-dense: no f32 array it writes is padded to over twice its
    values, and no (D, N) one-hot of a gather is written."""
    rel = ("pu_zone", "do_zone", "day", "hour", "payment_type", "rate_code")
    doms = {"pu_zone": 265, "do_zone": 265, "day": 31, "hour": 24,
            "payment_type": 6, "rate_code": 7, "·0": 7}
    in_canon = (("pu_zone", "·0"), ("do_zone",), ("day",))
    members = ({"·0": 7}, {"·0": 7})
    plan = plans_mod._build_batched_sparse_plan(
        sr.SUM, rel, doms, in_canon, ("hour",), ("·0",), ROWS, members
    )
    assert plan.gathers == (4, 0)

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    rows = sds((ROWS,), jnp.int32)
    fields = (sds((265, 7)), sds((265,)), sds((31,)))
    hlo = _compile(
        plan.fn,
        (sds((ROWS,)),) * 2, (fields,) * 2, (rows,) * 3,
        ((sds((24,), jnp.bool_),),) * 2, (rows,), rows,
    ).as_text()
    written = list(_written(hlo, "rowwise"))
    assert any(s.group(1) == "f32" for s, _ in written)
    for shape, line in written:
        dims = [int(x) for x in shape.group(2).split(",") if x]
        assert 265 not in dims, line
        if shape.group(1) != "f32":
            continue
        dense, tiled = _tiled_bytes(*shape.groups()[1:], 4)
        assert tiled <= 2 * dense, line
