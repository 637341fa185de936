"""Record the profiler trace that ``test_program_trace.py`` reads.

Run on a TPU: ``python3 bench/tests/record_program_trace.py <directory>``.
Under the program's own host spans it runs two compiled sparse plans over
the same rows: one whose segment reduction is the Pallas kernel, one whose
ring has no kernel op (``kernel_segment_op`` None), so XLA reduces.
Between them the host sleeps inside a ``treant.session.derive`` span, a gap
with no device work.  The ``.xplane.pb`` is copied to
``bench/tests/data/program_spans.xplane.pb``.
"""

from __future__ import annotations

import dataclasses
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.profiler import TraceAnnotation  # noqa: E402

from bench import trace_reduce  # noqa: E402
from repro.core import plans  # noqa: E402
from repro.core import semiring as sr  # noqa: E402
from repro.trace import span  # noqa: E402

N, A, B, C = 1 << 16, 256, 64, 2  # rows; the fact table's attributes; one carried
FALLBACK = dataclasses.replace(sr.SUM, kernel_segment_op=None)


def plan(ring):
    """A message over ``b`` and ``c`` absorbed into the fact bag (a, b),
    out to ``(a, c)``: ``c`` rides as the kernel slab's second value column,
    as a viz grouped by a dimension's attribute does."""
    return plans._build_sparse_plan(ring, ("a", "b"), {"a": A, "b": B, "c": C},
                                    (("b", "c"),), (), ("a", "c"), N)


def main(out: str) -> int:
    if jax.devices()[0].platform != "tpu":
        print("needs a TPU", file=sys.stderr)
        return 2
    key = jax.random.key(0)
    a = jax.random.randint(jax.random.fold_in(key, 1), (N,), 0, A, jnp.int32)
    b = jax.random.randint(jax.random.fold_in(key, 2), (N,), 0, B, jnp.int32)
    vals = jax.random.uniform(jax.random.fold_in(key, 3), (N,), jnp.float32)
    message = jax.random.uniform(jax.random.fold_in(key, 4), (B, C), jnp.float32)
    args = (vals, (message,), (b,), (), (), a)
    kernel, fallback = plan(sr.SUM), plan(FALLBACK)
    assert kernel.uses_kernel and not fallback.uses_kernel
    jax.block_until_ready((kernel.fn(*args), fallback.fn(*args)))  # compile outside the trace
    jax.profiler.start_trace(out)
    with TraceAnnotation("serve.step"), span("treant.serve.step", batch=0, events=2):
        with span("treant.plans.run", kind="sparse"):
            first = kernel.fn(*args)
        with span("treant.serve.wait"):
            jax.block_until_ready(first)
        with span("treant.session.derive"):
            time.sleep(0.02)
        with span("treant.plans.run", kind="sparse"):
            second = fallback.fn(*args)
        with span("treant.serve.wait"):
            jax.block_until_ready(second)
    jax.profiler.stop_trace()
    src = trace_reduce.find(out)
    dst = ROOT / "bench" / "tests" / "data" / "program_spans.xplane.pb"
    shutil.copy(src, dst)
    print(f"copied {src} ({Path(src).stat().st_size} bytes) to {dst}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
