"""Where the Pallas kernels run: compiled by Mosaic on a TPU, interpreted elsewhere.

Every kernel entry point takes ``interpret=None`` and resolves it here, so
the choice follows the backend the process actually has.  An explicit
``interpret=False`` compiles for the TPU even when the default backend is the
CPU — ahead-of-time compiles against a described TPU topology rely on it.
Interpreting on a TPU backend would hide the chip, so it is refused.
"""

from __future__ import annotations

import jax


def resolve_interpret(interpret: bool | None) -> bool:
    on_tpu = jax.default_backend() == "tpu"
    if interpret is None:
        return not on_tpu
    if interpret and on_tpu:
        raise ValueError("Pallas interpret mode requested on a TPU backend")
    return bool(interpret)
