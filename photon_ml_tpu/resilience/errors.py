"""Typed transient-vs-fatal error classification + attributed failure types.

No reference analogue as code: the reference's failure model is Spark's —
lineage recompute re-executes lost partitions and the driver retries failed
tasks (spark-submit/YARN substrate, not a photon-ml source file; SURVEY.md
§5). The TPU-native stack has none of that substrate, so every host-side
boundary (Avro container reads, checkpoint files, coordination-service KV
exchanges, a device lost to preemption) needs an explicit answer to "is this
error worth retrying?". This module is that answer — ONE classifier every
retry/recovery site consults, so transient-vs-fatal policy lives in one
reviewed place instead of scattered ``except`` clauses (dev/lint_parity.py
bans broad excepts outside this layer's allowlist for exactly that reason).

Classification rules (in precedence order):

1. Explicit wrappers win: :class:`TransientError` is always transient;
   :class:`ExchangeTimeout` is always fatal (it is already ATTRIBUTED — the
   missing key/rank is named, and waiting the deadline again would just
   double the hang).
2. Known-poison signatures are fatal even when they smell transient:
   XLA's device OOM arrives as RESOURCE_EXHAUSTED, and re-dispatching the
   identical program runs out of memory identically.
3. Connection/timeout exception types and transient OS errnos (EAGAIN,
   EIO, ETIMEDOUT, ECONNRESET, ...) are transient.
4. Message patterns of the distributed runtimes (UNAVAILABLE,
   DEADLINE_EXCEEDED, "socket closed", "connection reset", ...) are
   transient — jaxlib surfaces coordination and device failures as
   RuntimeError subclasses whose TYPE carries no signal. Note what that
   admits: a chip held by another process also reads UNAVAILABLE, so a
   run that must prove it started pins ``--max-restarts 0`` and checks the
   ``resilience/*`` counters (chip_smoke.py does).
5. Everything else is fatal (ValueError, programming errors, divergence):
   retrying deterministic failures burns the budget and hides the bug.
"""

from __future__ import annotations

import enum
import errno
import re

#: OS errnos worth retrying: interrupted/expired I/O and dropped network
#: paths (a remote filesystem, the coordinator), never logic errors
TRANSIENT_ERRNOS = frozenset(
    {
        errno.EAGAIN,
        errno.EINTR,
        errno.EIO,
        errno.EBUSY,
        errno.ETIMEDOUT,
        errno.ECONNRESET,
        errno.ECONNABORTED,
        errno.ECONNREFUSED,
        errno.ENETRESET,
        errno.ENETUNREACH,
        errno.EHOSTUNREACH,
        errno.EPIPE,
    }
)

#: fatal-despite-the-smell signatures, checked BEFORE the transient
#: patterns. "out of memory" covers XLA's deterministic device OOM
#: ("RESOURCE_EXHAUSTED: Out of memory while trying to allocate ...") —
#: re-dispatching the identical program OOMs identically.
_FATAL_PATTERNS = re.compile(
    r"INVALID_ARGUMENT|out of memory",
    re.IGNORECASE,
)

#: gRPC/absl status words and socket-level phrases the distributed
#: runtimes put in RuntimeError messages for genuinely transient failures.
#: RESOURCE_EXHAUSTED stays here for its quota/rate-limit shape — the OOM
#: shape is intercepted by the fatal "out of memory" pattern above.
#: Device-loss / pool-preemption shapes (a preemptible TPU pool reclaiming
#: a worker surfaces as a lost-device XlaRuntimeError or a "Socket
#: closed"-class connection drop — the TYPE carries no signal) are transient
#: WITH-RESTART: the work is gone but a restarted attempt on a fresh
#: device resumes from the latest checkpoint (resilience/recovery.py).
_TRANSIENT_PATTERNS = re.compile(
    r"UNAVAILABLE|DEADLINE_EXCEEDED|RESOURCE_EXHAUSTED|ABORTED"
    r"|socket closed|connection reset|connection refused|broken pipe"
    r"|connection closed|temporarily unavailable|too many requests"
    r"|timed? ?out"
    r"|preempt(?:ed|ion)?|device (?:is )?lost|lost device"
    r"|device (?:failure|halted)|worker (?:has )?(?:restarted|terminated)",
    re.IGNORECASE,
)

#: the device-loss subset of the transient shapes: a preemptible pool
#: reclaiming the worker mid-run. Kept separate so drivers can tally
#: ``resilience/preemptions`` distinctly from garden-variety retries —
#: the counter that tells an operator their checkpoint cadence is being
#: exercised by the POOL, not by flaky I/O. A bare "socket closed" is
#: deliberately NOT here: it stays transient (restart-worthy), but a
#: dropped coordinator or filesystem connection reads the same — tallying
#: every one as a preemption would send the operator chasing the pool.
_PREEMPTION_PATTERNS = re.compile(
    r"preempt(?:ed|ion)?|device (?:is )?lost|lost device"
    r"|device (?:failure|halted)|worker (?:has )?(?:restarted|terminated)",
    re.IGNORECASE,
)

#: remediation hints keyed by fatal signature — logged once at giveup so
#: the next reader does not re-spend a round rediscovering the cause
FATAL_HINTS: tuple[tuple[re.Pattern, str], ...] = (
    (
        re.compile(r"out of memory", re.IGNORECASE),
        "device OOM is deterministic — retrying re-allocates identically; "
        "shrink the batch, use bf16 feature blocks, or shard further",
    ),
)


class Transience(enum.Enum):
    """The classifier's verdict: retry-worthy or not."""

    TRANSIENT = "transient"
    FATAL = "fatal"


class TransientError(RuntimeError):
    """Explicitly-transient failure: always retried within budget.

    Raise (or wrap a caught error in) this at call sites that KNOW the
    failure is worth retrying regardless of the generic rules."""


class ExchangeTimeout(TimeoutError):
    """A MetadataExchange read/barrier missed its deadline — attributed.

    Carries the exchange tag, the key that never appeared, and the rank(s)
    expected to publish it, so a wedged multi-host run fails with "rank 2
    never published partitioned_read/train" instead of an anonymous hang
    (the failure mode ISSUE 3 exists to kill). Classified FATAL: the
    deadline already waited; what is needed is the named rank's logs, not
    another identical wait. One exception to "fatal ends the job": a run
    with a ``resilience.coordinated.CoordinatedRecovery`` attached treats
    it (like :class:`PeerAbort`) as recoverable-VIA-COORDINATION — the
    coordinator rendezvouses every rank on an all-rank rollback instead of
    retrying the wait (ISSUE 15); without a coordinator the original
    contract stands.
    """

    def __init__(
        self,
        tag: str,
        *,
        missing_ranks: "tuple[int, ...] | list[int]" = (),
        key: str | None = None,
        rank: int | None = None,
        timeout: float | None = None,
        detail: str = "",
    ):
        self.tag = tag
        self.missing_ranks = tuple(int(r) for r in missing_ranks)
        self.key = key
        self.rank = rank
        self.timeout = timeout
        parts = [f"exchange {tag!r}"]
        if key is not None:
            parts.append(f"key {key!r} was never published")
        if self.missing_ranks:
            parts.append(
                "rank(s) %s did not participate"
                % ",".join(map(str, self.missing_ranks))
            )
        if rank is not None:
            parts.append(f"(observed on rank {rank})")
        if timeout is not None:
            parts.append(f"after {timeout:g}s")
        if detail:
            parts.append(f"[{detail}]")
        super().__init__(" ".join(parts))


class PeerAbort(RuntimeError):
    """ANOTHER rank aborted the attempt — attributed to the culprit.

    Raised by a generation-fenced exchange wait when a peer rank posts an
    abort marker (its own failure classified transient/preemption) instead
    of publishing its key: the healthy ranks fail FAST with the culprit
    rank and cause named, rather than burning the full exchange deadline
    on a rank that already knows it is restarting. Classified FATAL for
    the same reason as :class:`ExchangeTimeout` — already attributed, and
    blindly re-waiting would desynchronize the SPMD call sequence — but
    recoverable VIA COORDINATION: ``run_with_recovery(coordinator=...)``
    turns it into an all-rank rollback to the last barrier-committed
    checkpoint (resilience/coordinated.py).
    """

    def __init__(
        self,
        tag: str,
        *,
        origin_rank: "int | None" = None,
        cause: str = "",
        generation: int | None = None,
        rank: int | None = None,
    ):
        self.tag = tag
        self.origin_rank = origin_rank
        self.cause = cause
        self.generation = generation
        self.rank = rank
        parts = [f"exchange {tag!r} aborted"]
        if origin_rank is not None:
            parts.append(f"by rank {origin_rank}")
        else:
            parts.append("by an unattributed peer (corrupt abort marker?)")
        if generation is not None:
            parts.append(f"in generation {generation}")
        if cause:
            parts.append(f"cause: {cause}")
        if rank is not None:
            parts.append(f"(observed on rank {rank})")
        super().__init__(" ".join(parts))


def classify_exception(exc: BaseException) -> Transience:
    """The ONE transient-vs-fatal rule (precedence in the module docstring)."""
    if isinstance(exc, TransientError):
        return Transience.TRANSIENT
    if isinstance(exc, (ExchangeTimeout, PeerAbort)):
        # already-attributed coordination failures: the cause STRING may
        # smell transient ("preempted"), but re-waiting/retrying locally
        # would desync the SPMD sequence — only the coordinator path
        # (resilience/coordinated.py) may recover these
        return Transience.FATAL
    message = f"{type(exc).__name__}: {exc}"
    if _FATAL_PATTERNS.search(message):
        return Transience.FATAL
    if isinstance(
        exc, (ConnectionError, TimeoutError, InterruptedError)
    ):
        return Transience.TRANSIENT
    if isinstance(exc, OSError) and exc.errno in TRANSIENT_ERRNOS:
        return Transience.TRANSIENT
    if _TRANSIENT_PATTERNS.search(message):
        return Transience.TRANSIENT
    return Transience.FATAL


def is_transient(exc: BaseException) -> bool:
    return classify_exception(exc) is Transience.TRANSIENT


def is_preemption(exc: BaseException) -> bool:
    """True for transient failures whose shape is a device loss / pool
    preemption (a lost-device XlaRuntimeError) rather than ordinary flaky
    I/O. Always a SUBSET of transient:
    a fatal-classified error (e.g. an OOM that happens to mention a
    device) is never counted as a preemption."""
    if classify_exception(exc) is not Transience.TRANSIENT:
        return False
    message = f"{type(exc).__name__}: {exc}"
    return bool(_PREEMPTION_PATTERNS.search(message))


def fatal_hint(exc: BaseException) -> str | None:
    """A remediation hint for known-fatal signatures, or None."""
    message = f"{type(exc).__name__}: {exc}"
    for pattern, hint in FATAL_HINTS:
        if pattern.search(message):
            return hint
    return None
