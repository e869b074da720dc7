"""The program's names for its own work, as a ``jax.profiler`` trace
shows them.  This module is the program's only tracing facility; it has
no switch.

* :func:`span` opens a host span (``jax.profiler.TraceAnnotation``) on
  the profiler's host clock.  With no profile being taken it costs one
  no-op check.
* :func:`scope` opens a device scope (``jax.named_scope``) while a
  function is traced.  It exists at compile time only: the name lands in
  the ``op_name`` metadata of every HLO instruction built inside it,
  fusions included, so a device op event can be traced back to the code
  that emitted it.  It costs nothing at run time.

Scopes nest; an instruction's ``op_name`` holds the whole path, e.g.
``.../circulant.reduce/roundstep.acc_shuffle/...``.
"""

from __future__ import annotations

import jax

# Host spans: one CALL per plan call, with VALIDATE (the payload check)
# and EXECUTE (the jitted executor's dispatch) nested in it.
CALL = "circulant.call"
VALIDATE = "circulant.validate"
EXECUTE = "circulant.execute"

# Device scopes of the round-loop phases.
REDUCE = "circulant.reduce"
BCAST = "circulant.bcast"
ALLGATHER = "circulant.allgather"
ALLGATHERV = "circulant.allgatherv"
SCATTER = "circulant.scatter"
QREDUCE = "circulant.qreduce"
# Device scope of the quantized wire's per-block scales permutes, inside
# the ``circulant.qreduce`` and ``circulant.bcast`` phases (the int8
# payload's permutes stay in the phase's own scope).
SCALES = "circulant.scales"

# Device scopes of the slot layout around the round loop.
SPLIT = "circulant.split"        # payload -> slot buffers
JOIN = "circulant.join"          # slot buffers -> payload
REQUANT = "circulant.requant"    # root's final quantization
BUCKET = "gradsync.bucket"       # gradient tree <-> f32 buckets

# Device scopes of the round step, one per RoundStep method.
RS_PACK = "roundstep.pack"
RS_UNPACK = "roundstep.unpack"
RS_SHUFFLE = "roundstep.shuffle"
RS_SHUFFLE_STAGED = "roundstep.shuffle_staged"
RS_ACC_SHUFFLE = "roundstep.acc_shuffle"
RS_ACC_SHUFFLE_STAGED = "roundstep.acc_shuffle_staged"
RS_QACC_SHUFFLE = "roundstep.qacc_shuffle"


def span(name: str):
    """A host span named ``name`` (a context manager)."""
    return jax.profiler.TraceAnnotation(name)


def scope(name: str):
    """A device scope named ``name`` (a context manager)."""
    return jax.named_scope(name)
