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
