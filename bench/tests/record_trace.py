"""Record the small profiler trace that ``test_trace_reduce.py`` reads.

Run on a TPU: ``python3 bench/tests/record_trace.py <directory>``.  It
traces two segment-reduction kernel calls (one plain, one fused level
launch) between host spans named as the benchmark names its own, with a
host-only gap between them, and copies the ``.xplane.pb`` to
``bench/tests/data/segment_reduce.xplane.pb``.
"""

from __future__ import annotations

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
from repro.kernels.segment_aggregate import ops  # noqa: E402

N, G, V = 1 << 16, 256, 2


def main(out: str) -> int:
    if jax.devices()[0].platform != "tpu":
        print("needs a TPU", file=sys.stderr)
        return 2
    codes = jax.random.randint(jax.random.key(0), (N,), 0, G, jnp.int32)
    vals = jax.random.uniform(jax.random.key(1), (N, V), jnp.float32)
    one = jax.jit(lambda c, v: ops.aggregate_op(c, v, G, op="sum"))
    level = jax.jit(lambda c, v: ops.level_aggregate([(c, v, G), (c[: N // 2] % 64, v[: N // 2], 64)]))
    jax.block_until_ready((one(codes, vals), level(codes, vals)))  # compile outside the trace
    jax.profiler.start_trace(out)
    with TraceAnnotation("serve.step"):
        jax.block_until_ready(one(codes, vals))
    with TraceAnnotation("bench.wait"):
        time.sleep(0.02)
    with TraceAnnotation("serve.idle"):
        jax.block_until_ready(level(codes, vals))
    jax.profiler.stop_trace()
    src = trace_reduce.find(out)
    dst = ROOT / "bench" / "tests" / "data" / "segment_reduce.xplane.pb"
    shutil.copy(src, dst)
    print(f"copied {src} ({Path(src).stat().st_size} bytes) to {dst}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
