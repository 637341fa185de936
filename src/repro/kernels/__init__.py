# Pallas TPU kernels for the paper's compute hot-spots (compiled by Mosaic on a
# TPU backend, interpreted elsewhere — see mode.py):
#   semiring_contract  - MXU-tiled (+,x) message contraction with fused sigma
#   tropical_contract  - VPU-tiled (min,+)/(max,+) contraction
#   segment_aggregate  - sparse fact-table rows -> dense factor via one-hot matmul
from .semiring_contract import ops as semiring_ops  # noqa: F401
from .tropical_contract import ops as tropical_ops  # noqa: F401
from .segment_aggregate import ops as segment_ops  # noqa: F401
