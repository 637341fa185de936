"""The program's own marks on the profiler's timeline.

Two kinds, both read from one ``jax.profiler`` trace (``.xplane.pb``):

- **Host spans.**  :func:`span` is a ``jax.profiler.TraceAnnotation`` (a
  TraceMe) on the host plane, on the same clock as the device's
  operations.  The profiler records it only while a session runs
  (``jax.profiler.trace``); otherwise opening one costs under a
  microsecond.  Every span of the program goes through :func:`span`, and
  every name starts with ``treant.``; ``ids`` ride along as the event's
  stats (``treant.serve.step`` carries the batch number and its event
  count, which identify one step's spans).
- **Device scopes.**  ``jax.named_scope`` inside every compiled plan
  (``core/plans.py``): at trace time it names each operation of the block
  in the HLO metadata (``op_name``), which a TPU trace carries as each
  device operation's ``tf_op``.  It is metadata only and changes no
  compiled program.  The names:

  ============================  ==============================================
  ``rowwise``                   incoming-message gathers, ⊗ expansion, σ mask
  ``segment_reduce_<op>``       every segment reduction, kernel or XLA, with
                                its pads, transposes and copies
  ``finalize``                  reshape of the reduced segments, ``project_to``
  ``batch_stage``               padding and stacking of a vmapped batch
  ``batch_slice``               slicing each member out of a batch
  ``row_blocks``                splitting a large body into row blocks and
                                ⊕-combining their partial factors
  ``dense_contract``            the dense plan's body
  ``cube_slice``                compiled bin-cube slices
  ============================  ==============================================
"""

from __future__ import annotations

from jax.profiler import TraceAnnotation


def span(name: str, **ids) -> TraceAnnotation:
    """A host span named ``name`` (``treant.<layer>.<stage>``)."""
    return TraceAnnotation(name, **ids)
