"""Multi-host / multi-slice process coordination and hybrid meshes.

No reference analogue as code: the reference's multi-node story is the
Spark driver/executor runtime (cluster bootstrap belonged to spark-submit
and YARN, not to any photon-ml source file) — YARN launches executors, the
driver coordinates, and all communication is shuffle/broadcast/treeAggregate
(SURVEY.md §2.5 — "Distributed communication backend"). The TPU-native
equivalent is:

- process coordination: ``jax.distributed.initialize`` — every host runs the
  same SPMD program, a coordinator rendezvouses them (this file);
- collectives: XLA over ICI within a slice, DCN across slices — chosen by
  device order in the mesh, not by hand-written NCCL/MPI calls.

``initialize()`` is a thin, idempotent wrapper suitable for CLI drivers:
single-process runs (tests, a one-host TPU VM) skip coordination entirely,
multi-host runs pick up the standard cluster-env variables (GKE/GCE
metadata) or explicit arguments — and fail if the rendezvous does.

``make_hybrid_mesh()`` builds the ("data", "model") mesh the rest of the
framework assumes (parallel/mesh.py), but topology-aware for multi-slice
pods: the "model" (feature/tensor) axis — which carries the per-L-BFGS-step
all-gathers and reduce-scatters of giant fixed-effect coordinates — is laid
out over ICI inside a slice, while the "data" axis (sample/entity DP, one
psum per objective evaluation) spans the slower DCN between slices. This is
the standard scaling-book layout: chatty axes ride fast links.
"""

from __future__ import annotations

import itertools
import json
import logging
import re
import threading

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding

from photon_ml_tpu.telemetry import tracing

logger = logging.getLogger(__name__)

_INITIALIZED = False


def initialize(
    coordinator_address: str | None = None,
    num_processes: int | None = None,
    process_id: int | None = None,
    local_device_ids=None,
) -> None:
    """Idempotently initialize multi-host JAX.

    No-op when nothing indicates a multi-process run (no arguments and no
    cluster environment), so drivers can call it unconditionally — the same
    binary then works on a laptop CPU, one TPU chip, or a multi-host pod
    (the reference's spark-submit local[*] vs YARN split, without the two
    code paths).
    """
    global _INITIALIZED
    if _INITIALIZED:
        return
    explicit = (
        coordinator_address is not None
        or num_processes is not None
        or process_id is not None
    )
    if not explicit:
        import os

        cluster_vars = (
            "COORDINATOR_ADDRESS",  # explicit
            "MEGASCALE_COORDINATOR_ADDRESS",  # multislice
        )
        # A one-host TPU VM exports the worker variables too — the v5e
        # host reads TPU_WORKER_HOSTNAMES=localhost, TPU_WORKER_ID=0 and no
        # coordinator — so the list counts only when it names several hosts.
        multi_worker = "," in os.environ.get("TPU_WORKER_HOSTNAMES", "")
        if not (multi_worker or any(os.environ.get(v) for v in cluster_vars)):
            logger.debug("single-process run; skipping jax.distributed")
            return
    # an environment that says "several processes" and cannot rendezvous
    # raises: training on as one process would read as a healthy run
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
        local_device_ids=local_device_ids,
    )
    _INITIALIZED = True
    logger.info(
        "jax.distributed initialized: process %d/%d, %d local / %d global devices",
        jax.process_index(),
        jax.process_count(),
        jax.local_device_count(),
        jax.device_count(),
    )


def make_hybrid_mesh(
    data: int | None = None,
    model: int = 1,
    *,
    devices=None,
) -> Mesh:
    """("data", "model") mesh, topology-aware across slices.

    Single-slice (or CPU) topologies fall back to a plain reshape (identical
    to parallel/mesh.make_mesh). On multi-slice TPU topologies the mesh is
    built with ``mesh_utils.create_hybrid_device_mesh`` so the "model" axis
    stays inside a slice (ICI) and only the "data" axis crosses DCN.
    """
    devices = list(devices if devices is not None else jax.devices())
    if data is None:
        data = len(devices) // model
    if data * model > len(devices):
        raise ValueError(
            f"mesh {data}x{model} needs {data * model} devices, have {len(devices)}"
        )
    devices = devices[: data * model]

    num_slices = len({getattr(d, "slice_index", 0) for d in devices})
    if num_slices > 1:
        from jax.experimental import mesh_utils

        per_slice = len(devices) // num_slices
        if data % num_slices != 0 or model > per_slice:
            raise ValueError(
                f"hybrid mesh {data}x{model} cannot split over {num_slices} "
                "slices: the data axis must be divisible by the slice count "
                "and the model axis must fit inside one slice"
            )
        grid = mesh_utils.create_hybrid_device_mesh(
            mesh_shape=(data // num_slices, model),
            dcn_mesh_shape=(num_slices, 1),
            devices=devices,
        )
    else:
        grid = np.array(devices).reshape(data, model)
    return Mesh(grid, axis_names=("data", "model"))


def default_put():
    """The host->sharding placement function for the current topology:
    :func:`global_put` when the program spans processes (plain device_put
    cannot target shardings that include other processes' devices),
    ``jax.device_put`` otherwise. The one selection rule shared by the
    training path (distributed.train_distributed) and the scorer."""
    if jax.process_count() > 1:
        return global_put
    return jax.device_put


def global_put(arr, sharding):
    """Place a host array onto a (possibly multi-process) sharding.

    Works where plain ``jax.device_put`` may not: when the sharding spans
    devices of OTHER processes, each process materializes only its
    addressable shards from its own (identical) copy of the full array —
    the standard way to feed replicated host data into a multi-host SPMD
    program. Single-process it degrades to an ordinary placement, so it is
    a drop-in ``put_fn`` for GameTrainProgram.shard_inputs on pods.

    Host numpy inputs are sliced zero-copy; a device-resident input costs
    one device-to-host read first (prepare_inputs materializes pytrees on
    the local device), so at pod scale feed host-built arrays where the
    input pipeline allows.
    """
    value = np.asarray(arr)  # zero-copy for host numpy inputs
    return jax.make_array_from_callback(
        value.shape, sharding, lambda idx: value[idx]
    )


# ---------------------------------------------------------------------------
# Host-side metadata exchange (partitioned I/O)
# ---------------------------------------------------------------------------
#
# The partitioned host-I/O layer (io/partitioned_reader.py,
# io/score_writer.py) needs each rank to agree on SMALL metadata — feature
# keys, entity vocabularies + counts, per-rank row counts, part numbering —
# without any rank reading the other ranks' bytes. The reference gets this
# from Spark's driver (a JVM object broadcast); the TPU-native equivalent
# rides jax.distributed's coordination service KV store: a host-side
# channel that works before (and independently of) any device computation,
# so ingestion metadata can rendezvous while the accelerator program is
# still being built. NOT for bulk data — payloads are JSON and should stay
# well under a few MB; array-sized exchanges belong on the devices.


#: default deadline (seconds) for exchange reads and barriers — generous
#: enough for a slow rank's multi-GB local decode, bounded enough that a
#: wedged run fails attributed instead of hanging a CI/driver forever
DEFAULT_EXCHANGE_TIMEOUT = 120.0


class MetadataExchange:
    """Rank-aware small-payload allgather + barrier for host-side I/O.

    Every rank must make the SAME sequence of calls (SPMD discipline, like
    collectives); tags are namespaced per call site and serialized with an
    internal counter so repeated exchanges never collide.

    Every read/barrier carries a DEADLINE: a rank that never publishes its
    key (crashed, wedged, skipped a collective) surfaces as a
    rank-attributed ``resilience.errors.ExchangeTimeout`` naming the tag,
    the missing key, and the rank expected to publish it — never an
    unbounded hang (ISSUE 3). Retry does NOT belong here: re-waiting one
    rank's exchange while the others do not desynchronizes the SPMD call
    sequence (resilience/policy.py module doc).

    GENERATION FENCING (ISSUE 15, used by resilience/coordinated.py):
    ``set_generation(g)`` moves every subsequent key/barrier id into a
    generation-``g`` namespace AND resets the per-instance call sequence,
    so a restarted attempt (whose ranks died at different points of the
    SPMD sequence, leaving their counters desynchronized) resynchronizes
    at seq 0 of the new generation — and a dead attempt's stale keys,
    living in the old generation's namespace, can never satisfy a new
    generation's get. ``generation=None`` (the default) is the legacy
    unfenced keyspace, byte-identical to pre-ISSUE-15 behavior.

    ABORT MARKERS: ``post_abort(info)`` best-effort-publishes a rank- and
    cause-attributed marker for the CURRENT generation; a fenced wait that
    observes a peer's marker raises a typed
    ``resilience.errors.PeerAbort`` naming the culprit instead of burning
    the full deadline. Markers are written ONLY on the failure path and
    checked only inside waits that are already blocked — a healthy run
    performs ZERO additional exchange operations.
    """

    rank: int = 0
    num_ranks: int = 1
    #: current fence generation (None = unfenced legacy keyspace)
    generation: "int | None" = None
    #: fence EPOCH: distinguishes successive fencing sessions over one
    #: transport (e.g. a driver ``run()`` called twice in one process, each
    #: attaching its own coordinator) — a new session's generation-0 keys
    #: must never collide with a previous session's. Incremented whenever a
    #: NEW fence starts (first ``set_generation``, or a non-increasing
    #: generation); SPMD-consistent because every rank fences at the same
    #: logical points.
    fence_epoch: int = 0

    def allgather(self, tag: str, payload) -> list:
        """All ranks' ``payload``s (JSON-able), ordered by rank."""
        raise NotImplementedError

    def barrier(self, tag: str) -> None:
        """Block until every rank reaches this barrier."""
        raise NotImplementedError

    def set_generation(self, generation: int) -> None:
        """Adopt the generation-``generation`` key namespace and reset the
        per-instance call sequence (every rank calls at the same logical
        point — the coordinator's restart rendezvous — so sequences agree
        again even after a mid-sequence death). A non-increasing generation
        starts a new fence EPOCH (see ``fence_epoch``)."""
        generation = int(generation)
        if self.generation is None or generation <= self.generation:
            self.fence_epoch += 1
        self.generation = generation

    def post_abort(self, info: dict) -> None:
        """Best-effort: publish an abort marker for the current generation
        (``info`` carries at least ``rank`` and ``cause``). Default: no-op
        (no peers to warn)."""

    def pending_abort(self) -> "dict | None":
        """A PEER's abort marker for the current generation, or None.
        Markers this rank posted itself are never returned (the culprit is
        already restarting; it must not abort on its own marker)."""
        return None

    def _shape_marker(self, marker) -> "dict | None":
        """Normalize a raw abort marker for ``pending_abort``: a corrupt
        (non-dict) payload still ends the wait typed and bounded — just
        unattributed (dev/faultinject.abort_marker_corruptor pins this);
        this rank's own marker is invisible."""
        if marker is None:
            return None
        if not isinstance(marker, dict):
            return {"rank": None, "cause": f"unparseable marker {marker!r}"}
        if marker.get("rank") == self.rank:
            return None
        return marker

    def _raise_abort(self, tag: str, marker: dict):
        """Raise the typed, culprit-attributed PeerAbort for ``marker``
        (one construction site for every transport)."""
        from photon_ml_tpu.resilience.errors import PeerAbort

        origin = marker.get("rank")
        raise PeerAbort(
            tag,
            origin_rank=None if origin is None else int(origin),
            cause=str(marker.get("cause", "")),
            generation=self.generation,
            rank=self.rank,
        )


class SingleProcessExchange(MetadataExchange):
    """The trivial exchange: one rank, no waiting. Still traced (zero-wait
    spans) so a single-process timeline shows where exchanges would sit."""

    def allgather(self, tag: str, payload) -> list:
        with tracing.span("exchange/allgather", cat=tracing.EXCHANGE_CAT,
                          tag=tag, rank=self.rank):
            return [payload]

    def barrier(self, tag: str) -> None:
        with tracing.span("exchange/barrier", cat=tracing.EXCHANGE_CAT,
                          tag=tag, rank=self.rank):
            return None


class InProcessExchange(MetadataExchange):
    """N virtual ranks inside one process (threads) — the test/simulation
    transport: lets the partitioned reader/writer run num_ranks>1 flows on
    a single host, e.g. against the virtual CPU mesh."""

    def __init__(self, store: dict, rank: int, num_ranks: int,
                 timeout: float = DEFAULT_EXCHANGE_TIMEOUT):
        self._store = store
        self.rank = rank
        self.num_ranks = num_ranks
        self.timeout = float(timeout)
        # per-instance call counter: repeated exchanges under the SAME tag
        # stay distinct (every rank makes the same sequence of calls — the
        # SPMD discipline — so counters agree), mirroring the KV transport
        self._seq = 0

    @classmethod
    def create_group(
        cls, num_ranks: int, timeout: float = DEFAULT_EXCHANGE_TIMEOUT
    ) -> "list[InProcessExchange]":
        store = {
            "cond": threading.Condition(),
            "gather": {},
        }
        return [cls(store, r, num_ranks, timeout=timeout)
                for r in range(num_ranks)]

    def set_generation(self, generation: int) -> None:
        super().set_generation(generation)
        # resync: every rank adopts the new namespace at the same logical
        # point (the coordinator's restart rendezvous), so resetting the
        # per-instance counter re-agrees the sequences even though the
        # ranks died at different points of the old one
        self._seq = 0

    def post_abort(self, info: dict) -> None:
        cond = self._store["cond"]
        with cond:
            # first writer wins per (epoch, generation): the marker
            # attributes the FIRST failure; a second rank failing in the
            # same window is a casualty, not a new culprit
            self._store.setdefault("aborts", {}).setdefault(
                (self.fence_epoch, self.generation),
                dict(info) if isinstance(info, dict) else info,
            )
            # wake every rank blocked in a wait_for — their predicates
            # consult pending_abort() below
            cond.notify_all()

    def pending_abort(self) -> "dict | None":
        return self._shape_marker(
            self._store.get("aborts", {}).get(
                (self.fence_epoch, self.generation)
            )
        )

    def allgather(self, tag: str, payload) -> list:
        from photon_ml_tpu.resilience.errors import ExchangeTimeout

        key = (self.fence_epoch, self.generation, self._seq, tag)
        self._seq += 1
        cond, slot = self._store["cond"], self._store["gather"]
        # the span OBSERVES the blocking wait (tag + seq + rank for the
        # straggler tables); it never gates or reorders the exchange
        with tracing.span("exchange/allgather", cat=tracing.EXCHANGE_CAT,
                          tag=tag, seq=key[2], rank=self.rank), cond:
            entry = slot.setdefault(key, {})
            entry[self.rank] = payload
            cond.notify_all()
            cond.wait_for(
                lambda: len(slot[key]) == self.num_ranks
                or self.pending_abort() is not None,
                timeout=self.timeout,
            )
            if len(slot[key]) != self.num_ranks:
                marker = self.pending_abort()
                if marker is not None:
                    # a peer declared the attempt dead: fail fast
                    # attributed instead of burning the rest of the
                    # deadline on a rank that is already restarting
                    self._raise_abort(tag, marker)
                missing = [r for r in range(self.num_ranks)
                           if r not in slot[key]]
                raise ExchangeTimeout(
                    tag,
                    missing_ranks=missing,
                    rank=self.rank,
                    timeout=self.timeout,
                    detail=f"{len(slot[key])}/{self.num_ranks} ranks "
                           "published",
                )
            out = [slot[key][r] for r in range(self.num_ranks)]
            # reclaim the slot once every rank has read it (payloads can
            # be sizable — feature-key lists — and exchanges are many)
            reads = self._store.setdefault("reads", {})
            reads[key] = reads.get(key, 0) + 1
            if reads[key] == self.num_ranks:
                del slot[key]
                del reads[key]
            return out

    def barrier(self, tag: str) -> None:
        self.allgather(f"__barrier__/{tag}", None)


#: process-global sequence for KV keys/barrier ids: the coordination
#: service's namespace is process-wide, so two exchange INSTANCES in one
#: process (e.g. a driver run() called twice) must never reuse a key or a
#: barrier id. Every rank constructs/calls exchanges in the same order
#: (SPMD discipline), so the counters agree across processes.
_kv_seq = itertools.count().__next__


#: how jaxlib's coordination-service client spells a missed deadline in
#: the RuntimeError it raises (the TYPE carries no signal)
_KV_DEADLINE_RE = re.compile(r"deadline|timed? ?out", re.IGNORECASE)


class DistributedKVExchange(MetadataExchange):
    """Multi-process transport over jax.distributed's coordination-service
    key-value store (the same rendezvous channel ``initialize`` uses) —
    host-side only, so partitioned ingestion metadata flows even before
    the first device computation.

    Resilience wiring: point-to-point KV set/get operations retry
    classified-transient coordinator errors (resilience/policy.py's KV
    policy — a retried set that finds its key already stored treats the
    first attempt as delivered); a blocking get or barrier that misses
    its deadline raises a rank-attributed
    ``resilience.errors.ExchangeTimeout`` naming the missing key and the
    rank expected to publish it. Barriers are never retried (barrier ids
    are single-use; only the deadline mapping applies).

    ``client``/``rank``/``num_ranks`` are injectable for chaos tests —
    production callers leave them None and get the live coordination
    client.
    """

    def __init__(self, timeout_ms: int = 120_000, *, client=None,
                 rank: int | None = None, num_ranks: int | None = None,
                 retry=None):
        if client is None:
            from jax._src import distributed

            client = distributed.global_state.client
            if client is None:
                raise RuntimeError(
                    "DistributedKVExchange needs jax.distributed.initialize "
                    "(multihost.initialize) to have run first"
                )
        self._client = client
        self._timeout_ms = timeout_ms
        self.rank = jax.process_index() if rank is None else int(rank)
        self.num_ranks = (
            jax.process_count() if num_ranks is None else int(num_ranks)
        )
        if retry is None:
            from photon_ml_tpu.resilience.policy import default_kv_policy

            retry = default_kv_policy()
        self._retry = retry
        #: per-instance sequence, used only in FENCED mode (generation set):
        #: within a generation every rank makes the same call sequence from
        #: the same reset point, so instance counters agree — and the
        #: (session nonce, generation) prefix keeps a restarted attempt —
        #: or a whole later fencing session — out of any dead keyspace.
        #: Unfenced mode keeps the process-global ``_kv_seq`` (two exchange
        #: instances in one process must not collide); fenced sessions get
        #: the same guarantee from the ``_fence_nonce`` drawn off that
        #: counter at fence time. ONE active fenced exchange per process,
        #: which the coordinator owns.
        self._gen_seq = 0
        self._fence_nonce = 0

    #: slice width for fenced blocking waits: between slices the wait
    #: checks the generation's abort key, so a peer's abort surfaces in
    #: ~this long instead of the full deadline. Only expired slices pay
    #: the extra read — a healthy (promptly-published) exchange performs
    #: zero additional operations.
    ABORT_POLL_MS = 500

    def set_generation(self, generation: int) -> None:
        new_fence = self.generation is None or int(
            generation
        ) <= self.generation
        super().set_generation(generation)
        if new_fence:
            # the coordination-service namespace is PROCESS-wide and its
            # barrier ids are single-use, so a second fencing session in
            # one process (driver run() called twice) must not reuse the
            # first session's e/g keyspace: draw the session nonce from
            # the process-global counter. SPMD-consistent — every rank
            # fences at the same logical point, so the draws agree.
            self._fence_nonce = _kv_seq()
        self._gen_seq = 0

    def _namespace(self) -> str:
        return f"e{self._fence_nonce}g{self.generation}"

    def _abort_key(self) -> str:
        return f"photon/abort/{self._namespace()}"

    def post_abort(self, info: dict) -> None:
        try:
            self._client.key_value_set(self._abort_key(), json.dumps(info))
        except RuntimeError as e:
            if "already_exists" in str(e).lower().replace(" ", "_"):
                return  # first writer wins per generation
            # best-effort by contract: the culprit is restarting either
            # way; peers fall back to their deadline (ExchangeTimeout)
            logger.warning("abort-marker write failed: %s", e)

    def pending_abort(self) -> "dict | None":
        if self.generation is None:
            return None
        try_get = getattr(self._client, "key_value_try_get", None)
        try:
            if try_get is not None:
                raw = try_get(self._abort_key())
            else:
                raw = self._client.blocking_key_value_get(
                    self._abort_key(), 1
                )
        except RuntimeError:
            return None  # absent key surfaces as an error: no marker
        try:
            marker = json.loads(raw)
        except (TypeError, ValueError):
            marker = raw  # corrupt payload: shaped unattributed below
        return self._shape_marker(marker)

    def _next_seq(self) -> int:
        if self.generation is None:
            return _kv_seq()
        seq, self._gen_seq = self._gen_seq, self._gen_seq + 1
        return seq

    def _key(self, tag: str, seq: int, rank: int) -> str:
        if self.generation is not None:
            return f"photon/xchg/{self._namespace()}/{seq}/{tag}/{rank}"
        return f"photon/xchg/{seq}/{tag}/{rank}"

    def _barrier_id(self, name: str) -> str:
        if self.generation is not None:
            return f"photon/bar/{self._namespace()}/{name}"
        return f"photon/bar/{name}"

    def _kv_set(self, key: str, value: str) -> None:
        def attempt():
            try:
                self._client.key_value_set(key, value)
            except RuntimeError as e:
                if "already_exists" in str(e).lower().replace(" ", "_"):
                    # a previous attempt's write landed but its ack was
                    # lost; keys are sequence-unique so the value matches
                    return
                raise

        with tracing.span("exchange/kv_set", cat=tracing.EXCHANGE_IO_CAT,
                          key=key, rank=self.rank):
            self._retry.call(attempt, description=f"kv_set {key}")

    def _kv_get(self, key: str, tag: str, expected_rank: int) -> str:
        from photon_ml_tpu.resilience.errors import ExchangeTimeout

        def timeout_error(e):
            return ExchangeTimeout(
                tag,
                key=key,
                missing_ranks=(expected_rank,),
                rank=self.rank,
                timeout=self._timeout_ms / 1000.0,
                detail=str(e),
            )

        def attempt():
            if self.generation is None:
                try:
                    return self._client.blocking_key_value_get(
                        key, self._timeout_ms
                    )
                except RuntimeError as e:
                    if _KV_DEADLINE_RE.search(str(e)):
                        raise timeout_error(e) from e
                    raise
            # fenced mode: slice the deadline so a peer's abort marker
            # surfaces within ~ABORT_POLL_MS instead of the full wait.
            # Only an EXPIRED slice pays the marker read — a promptly-
            # published key costs exactly one get, as before.
            remaining = int(self._timeout_ms)
            last = None
            while remaining > 0:
                chunk = min(self.ABORT_POLL_MS, remaining)
                try:
                    return self._client.blocking_key_value_get(key, chunk)
                except RuntimeError as e:
                    if not _KV_DEADLINE_RE.search(str(e)):
                        raise
                    last = e
                remaining -= chunk
                marker = self.pending_abort()
                if marker is not None:
                    self._raise_abort(tag, marker)
            raise timeout_error(last) from last

        with tracing.span("exchange/kv_get", cat=tracing.EXCHANGE_IO_CAT,
                          key=key, tag=tag, rank=self.rank):
            return self._retry.call(attempt, description=f"kv_get {key}")

    def _wait_barrier(self, barrier_id: str, tag: str) -> None:
        from photon_ml_tpu.resilience.errors import ExchangeTimeout

        try:
            self._client.wait_at_barrier(barrier_id, self._timeout_ms)
        except RuntimeError as e:
            if _KV_DEADLINE_RE.search(str(e)):
                # barrier ids are single-use, so the wait cannot be
                # sliced like a get: check the abort marker once at the
                # deadline so the failure is at least attributed
                marker = self.pending_abort()
                if marker is not None:
                    self._raise_abort(tag, marker)
                raise ExchangeTimeout(
                    tag,
                    key=barrier_id,
                    rank=self.rank,
                    timeout=self._timeout_ms / 1000.0,
                    detail=f"some rank never reached the barrier: {e}",
                ) from e
            raise

    def allgather(self, tag: str, payload) -> list:
        seq = self._next_seq()
        # one wait span per allgather (tag + seq + rank) — the kv_get/
        # kv_set sub-spans nest inside it; the straggler tables read only
        # this outer wait. Observes, never gates.
        with tracing.span("exchange/allgather", cat=tracing.EXCHANGE_CAT,
                          tag=tag, seq=seq, rank=self.rank):
            self._kv_set(self._key(tag, seq, self.rank), json.dumps(payload))
            out = []
            for r in range(self.num_ranks):
                raw = self._kv_get(self._key(tag, seq, r), tag, r)
                out.append(json.loads(raw))
            # every rank has read every key — reclaim our own entry so the
            # coordinator's KV store does not retain one payload per
            # exchange for the process lifetime (feature-key lists can be
            # MBs)
            self._wait_barrier(self._barrier_id(f"xchg-read/{seq}"), tag)
            try:
                self._client.key_value_delete(
                    self._key(tag, seq, self.rank)
                )
            except RuntimeError as e:
                # reclamation is best-effort; a leaked payload must not
                # fail an otherwise-complete exchange
                logger.warning("kv reclaim of %s failed: %s",
                               self._key(tag, seq, self.rank), e)
            return out

    def barrier(self, tag: str) -> None:
        with tracing.span("exchange/barrier", cat=tracing.EXCHANGE_CAT,
                          tag=tag, rank=self.rank):
            self._wait_barrier(
                self._barrier_id(f"{self._next_seq()}/{tag}"), tag
            )


def default_exchange() -> MetadataExchange:
    """The transport for the current topology: coordination-service KV when
    the program spans processes, the trivial exchange otherwise — the
    metadata twin of :func:`default_put`."""
    if jax.process_count() > 1:
        return DistributedKVExchange()
    return SingleProcessExchange()


def assemble_partitioned(
    blocks: "dict[int, np.ndarray]",
    mesh: Mesh,
    spec,
    num_ranks: int,
) -> jax.Array:
    """Global sharded array whose axis 0 is ``num_ranks`` equal-length
    per-rank blocks — each process supplies ONLY the blocks whose rows
    live on its addressable devices, so nothing of global size is ever
    materialized on one host (the partitioned twin of :func:`global_put`,
    built on ``jax.make_array_from_single_device_arrays``).

    blocks: rank -> [block_len, ...] host array; every provided block must
    share shape/dtype. Multi-process callers pass {my_rank: local_block};
    single-process simulations (virtual ranks on one host, tests) pass all
    of them. Requires the device layout to align rank blocks with
    addressable shards: the sharded axis size (num_ranks * block_len) must
    split so no device shard crosses a rank boundary.
    """
    sample = next(iter(blocks.values()))
    block_len = int(sample.shape[0])
    global_shape = (num_ranks * block_len,) + tuple(sample.shape[1:])
    sharding = NamedSharding(mesh, spec)
    arrays = []
    for dev, idx in sharding.addressable_devices_indices_map(
        global_shape
    ).items():
        sl = idx[0]
        start = 0 if sl.start is None else int(sl.start)
        stop = global_shape[0] if sl.stop is None else int(sl.stop)
        r = start // block_len if block_len else 0
        if stop > (r + 1) * block_len:
            raise ValueError(
                f"device shard rows [{start}, {stop}) cross the rank-"
                f"{r} block boundary (block_len={block_len}); pad each "
                "rank's block to a multiple of its local device count"
            )
        if r not in blocks:
            raise ValueError(
                f"device {dev} holds rows of rank {r} but no block for "
                f"that rank was provided (have {sorted(blocks)}); the "
                "mesh's device order must be process-contiguous along the "
                "sharded axis"
            )
        local = blocks[r][start - r * block_len: stop - r * block_len]
        rest = tuple(idx[1:])
        if rest:
            local = local[(slice(None),) + rest]
        arrays.append(jax.device_put(local, dev))
    return jax.make_array_from_single_device_arrays(
        global_shape, sharding, arrays
    )
