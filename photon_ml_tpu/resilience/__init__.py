"""Fault-tolerance layer: error classification, retry, deadlines, recovery.

Reference parity: the reference's fault tolerance is Spark's substrate —
RDD lineage recompute + task retries, owned by spark-submit/YARN rather
than any photon-ml source file (SURVEY.md §5). This package is the
explicit TPU-native replacement, wired through every host-side boundary;
see each submodule's docstring for its slice.
"""

from photon_ml_tpu.resilience.coordinated import (
    CoordinatedRecovery,
    RestartDecision,
)
from photon_ml_tpu.resilience.errors import (
    FATAL_HINTS,
    TRANSIENT_ERRNOS,
    ExchangeTimeout,
    PeerAbort,
    Transience,
    TransientError,
    classify_exception,
    fatal_hint,
    is_preemption,
    is_transient,
)
from photon_ml_tpu.resilience.policy import (
    RetryPolicy,
    default_io_policy,
    default_kv_policy,
)
from photon_ml_tpu.resilience.recovery import run_with_recovery

__all__ = [
    "FATAL_HINTS",
    "TRANSIENT_ERRNOS",
    "CoordinatedRecovery",
    "ExchangeTimeout",
    "PeerAbort",
    "RestartDecision",
    "Transience",
    "TransientError",
    "classify_exception",
    "fatal_hint",
    "is_preemption",
    "is_transient",
    "RetryPolicy",
    "default_io_policy",
    "default_kv_policy",
    "run_with_recovery",
]
