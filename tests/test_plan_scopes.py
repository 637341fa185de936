"""Device scopes inside every compiled plan.

Each stage of a plan body runs under a ``jax.named_scope`` (``repro.trace``)
so that a TPU trace can name the device time of each: ``rowwise``,
``segment_reduce_<op>`` for every segment reduction however it is
implemented, ``finalize``, ``batch_stage``/``batch_slice``,
``dense_contract``.  Each jitted plan body has a name of its own, so its
module names the plan's kind.  Scopes are HLO metadata only: the compiled
program is the same with and without them.
"""

import contextlib
import dataclasses
import re

import jax
import jax.numpy as jnp
import pytest
from jax._src.lib import xla_client

from repro.core import plans as plans_mod
from repro.core import semiring as sr

N = 256
DOMS = {"a": 8, "b": 5}


def _sds(shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype)


def _sparse_args():
    # (vals, in_fields, in_idx, pred_masks, pred_codes, seg_idx): a message
    # over b absorbed into the fact bag (a, b), σ on b, out to a
    return (_sds((N,)), (_sds((5,)),), (_sds((N,), jnp.int32),), (_sds((5,), jnp.bool_),),
            (_sds((N,), jnp.int32),), _sds((N,), jnp.int32))


def _batch_args(members=2):
    vals, fields, idx, masks, codes, seg = _sparse_args()
    return ((vals,) * members, (fields,) * members, idx, (masks,) * members, codes, seg)


def sparse_plan(ring=sr.SUM):
    return plans_mod._build_sparse_plan(ring, ("a", "b"), DOMS, (("b",),), ("b",), ("a",), N)


def batch_plan():
    return plans_mod._build_batched_sparse_plan(
        sr.SUM, ("a", "b"), DOMS, (("b",),), ("b",), ("a",), N, ({}, {}))


def level_plan():
    # one group of two members out to a, one single message out to b
    statics = (
        (("a", "b"), DOMS, (("b",),), ("b",), ("a",), N, ({}, {})),
        (("a", "b"), DOMS, (("a",),), (), ("b",), N, ({},)),
    )
    return plans_mod._build_level_plan(sr.SUM, statics)


def _level_args():
    vals, _, idx, _, _, seg = _sparse_args()
    single = ((vals,), ((_sds((8,)),),), idx, ((),), (), seg)
    return (_batch_args(), single)


def _dense():
    structs = ((("a", "b"), (8, 5)), (("b",), (5,)))
    plan = plans_mod._build_dense_plan(sr.SUM, structs, (("b", 0),), ("a",))
    return plan, ((_sds((8, 5)), _sds((5,))), (_sds((5,), jnp.bool_),))


@pytest.fixture
def kernels_on(monkeypatch):
    """Route every eligible reduction to the (interpreted) kernel, as a TPU
    process does."""
    monkeypatch.setattr(plans_mod, "_kernel_cost_max", lambda: 1 << 40)


def _scopes(lowered) -> set[str]:
    """Every element of the op-name paths in a lowered program's locations
    (``jit(level_plan)/vmap(rowwise)/gather``), transforms unwrapped; file
    names and Python function names left out."""
    out = set()
    for path in re.findall(r'loc\("([^"]*)"', lowered.as_text(debug_info=True)):
        if ".py" in path or "<" in path:
            continue
        for element in path.split("/"):
            while element.endswith(")") and "(" in element:
                out.add(element)
                element = element[element.index("(") + 1:-1]
            out.add(element)
    return out


def _lowered(plan, *args) -> set[str]:
    return _scopes(plan.fn.lower(*args))


CASES = {
    "sparse": (lambda: sparse_plan(), _sparse_args,
               ["jit(sparse_plan)", "rowwise", "segment_reduce_sum"]),
    "batched": (batch_plan, lambda: _batch_args(),
                ["jit(sparse_batch_plan)", "batch_stage", "rowwise", "segment_reduce_sum",
                 "batch_slice"]),
    "level": (level_plan, lambda: (_level_args(),),
              ["jit(level_plan)", "batch_stage", "rowwise", "segment_reduce_sum"]),
    "xla_fallback": (lambda: sparse_plan(dataclasses.replace(sr.SUM, kernel_segment_op=None)),
                     _sparse_args, ["jit(sparse_plan)", "rowwise", "segment_reduce_sum"]),
    "tropical": (lambda: sparse_plan(sr.TROPICAL_MAX), _sparse_args,
                 ["rowwise", "segment_reduce_max"]),
    "bool": (lambda: sparse_plan(sr.BOOL), _sparse_args, ["rowwise", "segment_reduce_bool"]),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_lowered_plan_names_its_stages(kernels_on, case):
    build, args, names = CASES[case]
    plan = build()
    scopes = _lowered(plan, *args())
    for name in names:
        assert name in scopes, (case, name)
    # the kernel branch and the XLA fallback both sit under the scope
    assert any("segment_aggregate" in s for s in scopes) == plan.uses_kernel, case


def test_finalize_names_the_reorder_to_the_output(kernels_on):
    # a message over (b, c) carries c off the bag; out to (c, a) transposes
    plan = plans_mod._build_sparse_plan(sr.SUM, ("a", "b"), {**DOMS, "c": 3}, (("b", "c"),),
                                        (), ("c", "a"), N)
    vals, _, idx, _, _, seg = _sparse_args()
    scopes = _lowered(plan, vals, (_sds((5, 3)),), idx, (), (), seg)
    assert {"finalize", "rowwise"} <= scopes


def test_dense_plan_names_its_body(kernels_on):
    plan, args = _dense()
    scopes = _lowered(plan, *args)
    assert {"jit(dense_plan)", "dense_contract"} <= scopes
    assert not any(s.startswith("segment_reduce") for s in scopes)


def test_row_blocked_body_keeps_its_scopes(kernels_on, monkeypatch):
    monkeypatch.setattr(plans_mod, "ROW_SLAB_BYTES", 64)
    monkeypatch.setattr(plans_mod, "_MIN_BLOCK_ROWS", 32)
    scopes = _lowered(sparse_plan(), *_sparse_args())
    assert {"row_blocks", "rowwise", "segment_reduce_sum"} <= scopes


def test_cube_slices_are_scoped():
    from repro.core.factor import Factor

    cube = Factor(("a", "b"), jnp.ones((8, 5)), sr.SUM)
    mask = jnp.ones((5,), jnp.bool_)
    one = plans_mod._compiled_slice("b", ("a",)).lower(cube, (mask,))
    batch = plans_mod._compiled_slice_batch((("b", ("a",)),)).lower((cube,), ((mask,),))
    for lowered in (one, batch):
        assert "cube_slice" in _scopes(lowered)


def _compiled_text(plan, *args) -> str:
    exe = plan.fn.lower(*args).compile().runtime_executable()
    options = xla_client._xla.HloPrintOptions.short_parsable()
    options.print_metadata = False
    return "\n".join(m.to_string(options) for m in exe.hlo_modules())


@pytest.mark.parametrize("case", ["batched", "level"])
def test_scopes_change_no_compiled_program(kernels_on, monkeypatch, case):
    build, args, _ = CASES[case]
    jax.clear_caches()
    scoped_names = _lowered(build(), *args())
    scoped = _compiled_text(build(), *args())
    monkeypatch.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
    jax.clear_caches()
    plan = build()
    assert "rowwise" not in _lowered(plan, *args())
    assert "rowwise" in scoped_names
    bare = _compiled_text(plan, *args())
    jax.clear_caches()
    assert scoped == bare
