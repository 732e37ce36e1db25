"""mp4j-resilience (ISSUE 5): fault injection + epoch-fenced recovery.

The reference is fail-stop (SURVEY.md section 5): no failure detection,
no retry, no way to even *test* failure behavior. This package is the
deliberate departure from that scope:

- :mod:`ytk_mp4j_tpu.resilience.faults` — a deterministic, seedable
  fault plan (``MP4J_FAULT_PLAN``) hooked into the socket transport:
  delay sends, cut a peer connection mid-frame, slow a rank, or kill a
  slave at the Nth collective. The substrate for the chaos grid in
  ``tests/test_resilience.py`` and for exercising the recovery engine.
- :mod:`ytk_mp4j_tpu.resilience.recovery` — the epoch-fenced
  abort/retry engine: on a transport failure the slave reports to the
  master over the control plane, the master broadcasts an abort round
  targeting ``epoch+1``, every rank tears down its peer channels (the
  drain — stale frames die with their connections, whose epoch is
  pinned at dial time), acks, and re-runs the failed collective from
  its preserved input once the master releases the round. Permanently
  dead ranks escalate to a terminal abort: every survivor raises the
  same clean ``Mp4jFatalError`` naming the dead rank — never a hang,
  never a partial result.
- :mod:`ytk_mp4j_tpu.resilience.membership` — elastic membership
  (ISSUE 10): warm-spare replacement and degraded shrink, both
  started by a rank's death; pure protocol functions + the master's
  spare pool and membership event log. ``Master.health_status()``
  only recommends: acting on a verdict is the operator's call.
"""

from ytk_mp4j_tpu.resilience.faults import (  # noqa: F401
    Fault, FaultInjector, FaultKill, FaultPlan)
from ytk_mp4j_tpu.resilience.recovery import (  # noqa: F401
    RECOVERABLE, RecoveryManager)
