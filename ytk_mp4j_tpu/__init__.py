"""ytk-mp4j-tpu: a TPU-native collective-communication framework.

A ground-up rebuild of the capabilities of ytk-mp4j (a pure-Java, MPI-like
message-passing library for distributed ML: gather / scatter / allgather /
reduce-scatter / broadcast / reduce / allreduce over dense arrays and sparse
``Map<K, V>`` operands, with pluggable reduction operators and a two-level
process x thread hierarchy — see SURVEY.md).

This rebuild is TPU-first:

- The hot path lowers collectives to XLA ICI collectives
  (``jax.lax.psum / psum_scatter / all_gather / ppermute``) under
  ``shard_map`` over a ``jax.sharding.Mesh`` (``comm.tpu_comm``).
- The reference's Kryo-over-TCP recursive-halving design is retained as a
  CPU reference implementation for differential testing
  (``comm.process_comm`` + ``comm.master``; build-plan phase 3), with the
  element-wise merge hot loop in native C++ (``csrc/mp4j_native.cpp``).
- Sparse map collectives pack to dense index/value buffers and ride the
  same ICI collectives (``ops.sparse``; build-plan phase 5).

Reference provenance: /root/reference was empty at survey time (SURVEY.md
paragraph 0); the API surface below is built from the capability list in
SURVEY.md section 2 and BASELINE.json, with naming chosen idiomatically.
"""

from ytk_mp4j_tpu.exceptions import Mp4jError
from ytk_mp4j_tpu.operators import Operator, Operators
from ytk_mp4j_tpu.operands import Operand, Operands
from ytk_mp4j_tpu import meta
from ytk_mp4j_tpu.utils import trace
from ytk_mp4j_tpu.utils.trace import trace_collectives

__version__ = "0.1.0"

__all__ = [
    "Mp4jError",
    "Operator",
    "Operators",
    "Operand",
    "Operands",
    "meta",
    "trace",
    "trace_collectives",
]
