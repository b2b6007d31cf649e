"""Bounded retry with exponential backoff and deterministic jitter.

No reference analogue as code: the reference delegates retry to Spark's
task scheduler (spark.task.maxFailures re-runs a lost partition's task;
no photon-ml source file implements it — SURVEY.md §5). Here the
equivalent is an explicit, typed :class:`RetryPolicy` wrapped around the
host-side boundaries the drivers own: remote-compile/dispatch call sites,
Avro container reads, and coordination-service KV operations
(parallel/multihost.DistributedKVExchange).

Design points:

- **Bounded**: ``max_attempts`` total calls; exhaustion re-raises the last
  error after counting a ``resilience/giveups``.
- **Classified**: only errors the shared classifier
  (resilience/errors.classify_exception) deems transient are retried —
  a ValueError or a device OOM burns zero retries.
- **Deterministic jitter**: backoff is ``base * multiplier**attempt``
  capped at ``max_delay``, stretched by a jitter fraction derived from a
  HASH of (policy name, call key, attempt) — reproducible run to run
  (no RNG state, no wall-clock dependence) yet decorrelated across ranks
  and call sites, which is what jitter exists for.
- **Observable**: every retry counts on ``resilience/retries`` and logs
  the classified error; giveups log the remediation hint for known-fatal
  signatures (errors.fatal_hint).

NOT for collectives: retrying one rank of an exchange/allgather while the
others do not desynchronizes the SPMD call sequence. Collective call
sites get deadlines (errors.ExchangeTimeout) instead; retry belongs
inside the transport's point-to-point operations (multihost._kv_* ) or
around whole single-process operations.
"""

from __future__ import annotations

import dataclasses
import hashlib
import logging
import time
from typing import Callable

from photon_ml_tpu.resilience.errors import (
    Transience,
    classify_exception,
    fatal_hint,
)
from photon_ml_tpu.telemetry import resilience_counters

logger = logging.getLogger(__name__)


def _jitter_fraction(name: str, key: str, attempt: int) -> float:
    """[0, 1) fraction from a stable hash — deterministic jitter."""
    digest = hashlib.blake2b(
        f"{name}/{key}/{attempt}".encode(), digest_size=4
    ).digest()
    return int.from_bytes(digest, "little") / 2**32


@dataclasses.dataclass(frozen=True)
class RetryPolicy:
    """``policy.call(fn, ...)`` — run ``fn`` with classified bounded retry.

    ``sleep`` is injectable so chaos tests pay zero wall-clock; everything
    else is data. Instances are immutable and shareable.
    """

    max_attempts: int = 3
    base_delay: float = 0.2
    max_delay: float = 30.0
    multiplier: float = 2.0
    #: extra delay of up to this fraction of the backoff, hash-derived
    jitter: float = 0.25
    name: str = "retry"
    classify: Callable[[BaseException], Transience] = classify_exception
    sleep: Callable[[float], None] = time.sleep

    def delay(self, attempt: int, key: str = "") -> float:
        """Backoff before attempt ``attempt + 1`` (attempt is 0-based)."""
        base = min(self.base_delay * self.multiplier**attempt, self.max_delay)
        return base * (1.0 + self.jitter * _jitter_fraction(self.name, key, attempt))

    def call(self, fn: Callable, *args, description: str = "", **kwargs):
        """Invoke ``fn(*args, **kwargs)``, retrying classified-transient
        failures up to ``max_attempts`` total attempts."""
        key = description or getattr(fn, "__name__", "call")
        attempt = 0
        while True:
            try:
                return fn(*args, **kwargs)
            except Exception as e:  # the classifier decides; see module doc
                if self.classify(e) is not Transience.TRANSIENT:
                    hint = fatal_hint(e)
                    if hint is not None:
                        logger.error(
                            "%s: %s failed with a known-fatal signature "
                            "(%r) — not retrying. Hint: %s",
                            self.name, key, e, hint,
                        )
                    raise
                attempt += 1
                if attempt >= self.max_attempts:
                    resilience_counters.record_giveup()
                    logger.error(
                        "%s: %s failed transiently %d/%d times; giving up "
                        "(last error: %r)",
                        self.name, key, attempt, self.max_attempts, e,
                    )
                    raise
                pause = self.delay(attempt - 1, key)
                resilience_counters.record_retry()
                logger.warning(
                    "%s: transient failure in %s (attempt %d/%d): %r — "
                    "retrying in %.2fs",
                    self.name, key, attempt, self.max_attempts, e, pause,
                )
                self.sleep(pause)


def default_io_policy() -> RetryPolicy:
    """Host I/O boundary (Avro container reads, checkpoint/journal files):
    a few quick attempts — local/remote filesystems either heal in seconds
    or not at all."""
    return RetryPolicy(max_attempts=3, base_delay=0.2, max_delay=5.0,
                       name="io-retry")


def default_kv_policy() -> RetryPolicy:
    """Coordination-service KV boundary: point-to-point set/get against
    the jax.distributed coordinator (deadlines are the transport's own
    job — see multihost.DistributedKVExchange)."""
    return RetryPolicy(max_attempts=3, base_delay=0.5, max_delay=10.0,
                       name="kv-retry")
