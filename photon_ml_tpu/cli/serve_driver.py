"""Serve driver: offline replay harness for the resident scoring service.

Reference parity: photon-client cli/game/scoring/GameScoringDriver.scala —
the reference's scoring entry point is a batch job; this driver is the
ONLINE half the ROADMAP's heavy-traffic north star needs, exercised
offline: it loads a GAME model ONCE into a resident scorer
(serving/resident.py), replays an Avro file of scoring records as a stream
of small requests through the micro-batching loop (serving/batching.py),
and reports the latency-SLO evidence — scores/sec, p50/p95 request
latency, pad fraction, compiled-signature count — against an embedded
SAME-RUN one-request-per-dispatch baseline (host-clock rates spread run to
run on a shared host; compare within a run).

The replay is deliberately closed-loop (submit as fast as the bounded
queue admits): it measures the service's steady-state ceiling, not an
arrival process.
"""

from __future__ import annotations

import argparse
import json
import logging
import os
import threading
import time
from typing import Sequence

from photon_ml_tpu.cli.configs import parse_feature_shard_config
from photon_ml_tpu.io.model_io import DEFAULT_COMPACT_RE_THRESHOLD
from photon_ml_tpu.io.partitioned_reader import read_partitioned
from photon_ml_tpu.util import Timed

logger = logging.getLogger(__name__)

DEFAULT_SHAPES = "64,256,1024"


class _SwapPoller(threading.Thread):
    """Continuous zero-downtime refresh (ROADMAP item 2 rider): watch a
    directory for ATOMICALLY-RENAMED model subdirectories and hot-swap
    each through the guarded ``MicroBatchServer.swap_model`` API while the
    serving loop keeps draining. Appearance == completeness (publishers
    stage under a ``tmp.*``/dot-prefixed sibling and ``os.rename`` into
    place — the checkpoint discipline), so a half-written model is never
    loaded. A rejected swap (``ModelSwapError``: layout change) or an
    unloadable dir journals a typed ``model_swap`` row and serving
    CONTINUES on the resident model — one bad publish never takes the
    service down."""

    def __init__(self, server, watch_dir: str, poll_s: float, *,
                 index_maps, compact_threshold: int, journal=None):
        super().__init__(name="serve-swap-poller", daemon=True)
        self._server = server
        self._watch_dir = watch_dir
        self._poll_s = max(poll_s, 1e-3)
        self._index_maps = index_maps
        self._compact_threshold = compact_threshold
        self._journal = journal
        self._stop_event = threading.Event()
        self._seen: set[str] = set()
        self.polls = 0
        self.applied: list[str] = []
        self.rejected: list[dict] = []

    def stop(self) -> None:
        self._stop_event.set()
        self.join(timeout=10.0)

    def run(self) -> None:
        while not self._stop_event.is_set():
            self.scan_once()
            self._stop_event.wait(self._poll_s)
        # one final scan so a model published just before the replay
        # drained is not silently skipped
        self.scan_once()

    def scan_once(self) -> None:
        from photon_ml_tpu.io.model_io import load_game_model
        from photon_ml_tpu.serving import ModelSwapError

        self.polls += 1
        try:
            names = sorted(os.listdir(self._watch_dir))
        except OSError:
            return  # the watch dir may not exist yet — keep serving
        for name in names:
            # staged (not yet renamed) publishes are invisible by contract
            if name in self._seen or name.startswith((".", "tmp.")):
                continue
            path = os.path.join(self._watch_dir, name)
            if not os.path.isdir(path):
                continue
            self._seen.add(name)
            try:
                model = load_game_model(
                    path, self._index_maps,
                    compact_random_effect_threshold=self._compact_threshold,
                )
                self._server.swap_model(model)
            except Exception as e:  # noqa: BLE001 — thread boundary (below)
                # a bad PUBLISH must never take the poller (and with it,
                # every future refresh) down: a garbled model dir can
                # raise beyond the obvious types (struct/zlib/EOF damage
                # inside an intact-looking dir), and this daemon thread
                # has no caller to re-raise to — so this is a reviewed
                # host-boundary catch (lint check 5 allowlist): every
                # failure is journaled typed and serving continues on the
                # resident model. A FATAL classification (programming
                # error) is additionally logged loudly with the class
                # named, so a systematic bug is not mistaken for bad
                # publishes.
                from photon_ml_tpu.resilience import is_transient

                self.rejected.append({"dir": name, "error": repr(e)})
                log = (
                    logger.warning
                    if isinstance(e, (ModelSwapError, OSError, ValueError,
                                      KeyError)) or is_transient(e)
                    else logger.error
                )
                log("rejected hot swap of %s: %r", path, e)
                if self._journal is not None:
                    self._journal.record(
                        "model_swap", dir=name, applied=False,
                        error=repr(e),
                    )
                continue
            self.applied.append(name)
            logger.info("hot-swapped model from %s", path)
            if self._journal is not None:
                self._journal.record("model_swap", dir=name, applied=True)


def _parse_shapes(spec: str) -> tuple[int, ...]:
    try:
        shapes = tuple(int(s) for s in spec.split(",") if s.strip())
    except ValueError:
        raise ValueError(f"bad --microbatch-shapes {spec!r}") from None
    if not shapes:
        raise ValueError("--microbatch-shapes names no shapes")
    return shapes


def run(
    *,
    requests_avro: str,
    model_input_dir: str,
    output_dir: str,
    feature_shards: dict | None = None,
    index_maps_dir: str | None = None,
    input_format: str = "avro",
    compact_random_effect_threshold: int = DEFAULT_COMPACT_RE_THRESHOLD,
    microbatch_shapes: "tuple[int, ...] | str" = DEFAULT_SHAPES,
    max_wait_ms: float = 2.0,
    queue_depth: int = 1024,
    request_rows: int = 1,
    num_requests: int | None = None,
    bf16: bool = False,
    skip_unbatched_baseline: bool = False,
    swap_model_dir: str | None = None,
    swap_at_request: int | None = None,
    swap_poll_ms: float = 0.0,
    telemetry_dir: str | None = None,
    trace_dir: str | None = None,
) -> dict:
    """Replay ``requests_avro`` as ``request_rows``-row requests through
    the resident micro-batch scorer; writes ``serving-summary.json`` and
    the served scores (``scores/part-*.avro``, ScoringResultAvro) under
    ``output_dir``.

    microbatch_shapes: the bucket set (power-of-two row counts) — the
    bound on compiled program signatures. max_wait_ms/queue_depth: the SLO
    knobs of the micro-batching loop. bf16: opt-in whole-path bf16
    features (not bitwise). skip_unbatched_baseline: drop the embedded
    one-request-per-dispatch comparison (it costs one dispatch and one
    host read per request).

    swap_model_dir: zero-downtime refresh rehearsal — a refreshed model
    (e.g. the incremental-refresh driver's output) hot-swapped IN-PLACE
    mid-replay through the guarded swap API while requests keep flowing;
    the summary's ``swap`` block carries the evidence (zero dropped
    requests, ledger-attributed score-program compiles across the swap ==
    0 on a same-layout model). swap_at_request: the submit index the swap
    fires before (default: halfway).

    swap_poll_ms > 0 switches ``swap_model_dir`` into CONTINUOUS mode
    (ROADMAP item 2 rider): the directory is WATCHED — every
    atomically-renamed model subdirectory that appears during the replay
    is loaded and hot-swapped in arrival order through the same guarded
    ``swap_model`` API (appearance == completeness: publishers must write
    to a ``tmp.*``/dot-prefixed sibling and ``os.rename`` into place, the
    checkpoint discipline). A rejected swap (layout change) journals a
    typed ``model_swap`` row and the loop KEEPS SERVING the resident
    model; the summary's ``swap`` block carries applied/rejected counts.

    telemetry_dir: rank-0 JSONL run journal (serve/* counters + latency
    histogram + phase timings) — written on the FAILURE path too.
    trace_dir: per-rank Chrome-trace span timelines; ``serve/`` spans
    observe the batching loop and dispatches, never gate them.
    """
    from photon_ml_tpu.telemetry import RunJournal
    from photon_ml_tpu.telemetry.resilience_counters import (
        reset_resilience_metrics,
    )
    from photon_ml_tpu.telemetry.serving_counters import reset_serving_metrics
    from photon_ml_tpu.util.timed import reset_timings, timing_summary

    # knowable before any load/warm work is paid: the two swap modes take
    # mutually exclusive knobs
    if swap_poll_ms > 0 and swap_at_request is not None:
        raise ValueError(
            "--swap-at-request names a submit index for the ONE-SHOT "
            "rehearsal swap, but --swap-poll-ms selects continuous mode, "
            "where swaps fire when a model dir APPEARS in "
            "--swap-model-dir; drop one of the two flags"
        )
    reset_timings()
    reset_resilience_metrics()
    reset_serving_metrics()
    journal = RunJournal(telemetry_dir) if telemetry_dir else None
    # the program ledger rides --telemetry-dir (ISSUE 13): every labeled
    # jit dispatch journals its compile/signature accounting, so a nonzero
    # replay compile count arrives WITH its attributed cause (the
    # program_recompile row naming the differing signature leaves)
    ledger = None
    if journal is not None:
        from photon_ml_tpu.telemetry.program_ledger import (
            ProgramLedger,
            install_ledger,
        )

        ledger = install_ledger(ProgramLedger(journal=journal))
    tracer = None
    if trace_dir:
        from photon_ml_tpu.telemetry.tracing import Tracer, install_tracer

        tracer = install_tracer(Tracer())
    succeeded = False
    try:
        summary = _run_inner(
            requests_avro=requests_avro,
            model_input_dir=model_input_dir,
            output_dir=output_dir,
            feature_shards=feature_shards,
            index_maps_dir=index_maps_dir,
            input_format=input_format,
            compact_random_effect_threshold=compact_random_effect_threshold,
            microbatch_shapes=microbatch_shapes,
            max_wait_ms=max_wait_ms,
            queue_depth=queue_depth,
            request_rows=request_rows,
            num_requests=num_requests,
            bf16=bf16,
            skip_unbatched_baseline=skip_unbatched_baseline,
            swap_model_dir=swap_model_dir,
            swap_at_request=swap_at_request,
            swap_poll_ms=swap_poll_ms,
            journal=journal,
        )
        succeeded = True
        if journal is not None:
            journal.record("serving_summary", **summary)
        return summary
    finally:
        if ledger is not None:
            from photon_ml_tpu.telemetry.program_ledger import uninstall_ledger

            uninstall_ledger()
        if tracer is not None:
            from photon_ml_tpu.telemetry.tracing import (
                flush_trace_best_effort,
                uninstall_tracer,
            )

            try:
                # best-effort: a publication error never masks the run's
                # own outcome or skips the journal rows below; the serve
                # driver is single-process, so no straggler merge
                flush_trace_best_effort(
                    tracer, trace_dir, exchange=None, gather=False,
                    journal=journal,
                )
            finally:
                uninstall_tracer()
        # failure-path journaling: the serve/* counters and the latency
        # histogram up to the failure are the post-mortem evidence
        if journal is not None:
            from photon_ml_tpu.telemetry import default_registry

            journal.record_timings(timing_summary())
            journal.record_metrics(default_registry().snapshot())
            journal.close()


def _run_inner(
    *,
    requests_avro: str,
    model_input_dir: str,
    output_dir: str,
    feature_shards: dict | None,
    index_maps_dir: str | None,
    input_format: str,
    compact_random_effect_threshold: int,
    microbatch_shapes,
    max_wait_ms: float,
    queue_depth: int,
    request_rows: int,
    num_requests: int | None,
    bf16: bool,
    skip_unbatched_baseline: bool,
    swap_model_dir: str | None = None,
    swap_at_request: int | None = None,
    swap_poll_ms: float = 0.0,
    journal=None,
) -> dict:
    import jax

    from photon_ml_tpu.cli.game_scoring_driver import _load_scoring_model
    from photon_ml_tpu.data.game_data import slice_game_dataset
    from photon_ml_tpu.serving import MicroBatchServer, ResidentScorer
    from photon_ml_tpu.telemetry import serving_counters
    from photon_ml_tpu.telemetry.probes import CompileMonitor, runtime_stamp

    if jax.process_count() > 1:
        raise ValueError(
            "serve_driver is single-process (one resident service per "
            "host); use game_scoring_driver --partitioned-io for "
            "multi-process batch scoring"
        )
    if request_rows <= 0:
        raise ValueError(f"request_rows must be positive, got {request_rows}")
    shapes = (
        _parse_shapes(microbatch_shapes)
        if isinstance(microbatch_shapes, str) else tuple(microbatch_shapes)
    )
    os.makedirs(output_dir, exist_ok=True)

    with Timed("load model"):
        model, index_maps, feature_shards, entity_vocabs, re_columns = (
            _load_scoring_model(
                model_input_dir=model_input_dir,
                index_maps_dir=index_maps_dir,
                feature_shards=feature_shards,
                compact_random_effect_threshold=(
                    compact_random_effect_threshold
                ),
            )
        )

    with Timed("read replay data"):
        from photon_ml_tpu.resilience import default_io_policy

        part = default_io_policy().call(
            lambda: read_partitioned(
                requests_avro,
                feature_shards,
                index_maps=index_maps or None,
                random_effect_id_columns=re_columns,
                entity_vocabs=entity_vocabs,
                fmt=input_format,
            ),
            description="read replay data",
        )
        dataset = part.result.dataset

    n = dataset.num_samples
    with Timed("slice requests"):
        requests = [
            slice_game_dataset(dataset, lo, min(lo + request_rows, n))
            for lo in range(0, n, request_rows)
        ]
        if num_requests is not None:
            requests = requests[:num_requests]
    total_rows = sum(r.num_samples for r in requests)
    logger.info(
        "replaying %d requests (%d rows) through shapes %s",
        len(requests), total_rows, shapes,
    )

    from photon_ml_tpu.telemetry.program_ledger import current_ledger

    ledger = current_ledger()
    scorer = ResidentScorer(model, shapes=shapes, bf16=bf16)
    if ledger is not None:
        ledger.set_phase("warm")
    with Timed("warm compile"), CompileMonitor() as warm_compiles:
        scorer.warm(requests[0])

    swap_model = None
    if swap_model_dir and swap_poll_ms <= 0:
        from photon_ml_tpu.io.model_io import load_game_model

        with Timed("load swap model"):
            # the SAME index maps as the resident model: an equal layout
            # is the whole point of a hot swap (the guard rejects a
            # mismatch typed, naming the differing leaves)
            swap_model = load_game_model(
                swap_model_dir, index_maps or None,
                compact_random_effect_threshold=(
                    compact_random_effect_threshold
                ),
            )
        if len(requests) < 2:
            raise ValueError(
                f"the replay has {len(requests)} request(s) but the "
                "mid-replay swap fires BETWEEN requests; raise "
                "--num-requests / shrink --request-rows, or drop "
                "--swap-model-dir"
            )
        if swap_at_request is None:
            swap_at_request = max(1, len(requests) // 2)
        # strict upper bound: the swap fires BEFORE submit index i, so
        # len(requests) would silently never fire
        if not 0 < swap_at_request < len(requests):
            raise ValueError(
                f"--swap-at-request {swap_at_request} is outside the "
                f"replay (1..{len(requests) - 1})"
            )

    unbatched_rate = None
    if not skip_unbatched_baseline:
        with Timed("unbatched baseline"):
            # the same-run baseline: one request per dispatch, no queue —
            # what a naive online scorer would do; its rate rides the
            # summary so the batched number is judged against THIS run's
            # chip and host only
            t0 = time.perf_counter()
            for r in requests:
                scorer.score(r)
            unbatched_rate = total_rows / max(
                time.perf_counter() - t0, 1e-9
            )
        # the baseline's latencies/counters are not the service's: reset
        # so the journaled histogram is the batched replay's alone
        from photon_ml_tpu.telemetry.serving_counters import (
            reset_serving_metrics,
        )

        reset_serving_metrics()

    if ledger is not None:
        # replay compiles are the SLO violation serving pins at zero: the
        # phase stamp makes any program_compile row from here on
        # attributable to the replay, not the warm-up
        ledger.set_phase("replay")
    swap_info = None
    with Timed("batched replay"), CompileMonitor() as replay_compiles:
        server = MicroBatchServer(
            scorer,
            max_wait_ms=max_wait_ms,
            queue_depth=queue_depth,
        )
        poller = None
        if swap_model_dir and swap_poll_ms > 0:
            poller = _SwapPoller(
                server, swap_model_dir, swap_poll_ms / 1e3,
                index_maps=index_maps or None,
                compact_threshold=compact_random_effect_threshold,
                journal=journal,
            )
        t0 = time.perf_counter()
        with server:
            if poller is not None:
                poller.start()
            try:
                futures = []
                for i, r in enumerate(requests):
                    if swap_model is not None and i == swap_at_request:
                        # the zero-downtime seam: swap IN-PLACE while the
                        # consumer keeps draining; a same-layout swap must
                        # compile nothing (the ledger delta below proves it)
                        pre = (
                            ledger.snapshot()
                            .get("serve/score", {}).get("compiles", 0)
                            if ledger is not None else None
                        )
                        server.swap_model(swap_model)
                        swap_info = {
                            "performed": True,
                            "at_request": i,
                            "_compiles_before": pre,
                        }
                    futures.append(server.submit(r))
                served = [f.result() for f in futures]
            finally:
                if poller is not None:
                    # stop INSIDE the server context — the final scan's
                    # swap still targets a live loop — and on the failure
                    # path too, so the thread never outlives the server
                    # or writes to a finalized journal
                    poller.stop()
        batched_sec = time.perf_counter() - t0
    batched_rate = total_rows / max(batched_sec, 1e-9)
    if poller is not None:
        swap_info = {
            "mode": "poll",
            "poll_ms": swap_poll_ms,
            "polls": poller.polls,
            "applied": list(poller.applied),
            "rejected": list(poller.rejected),
        }
    if swap_info is not None and "mode" not in swap_info:
        pre = swap_info.pop("_compiles_before")
        swap_info["score_compiles_after_swap"] = (
            None if pre is None else
            ledger.snapshot().get("serve/score", {}).get("compiles", 0) - pre
        )

    with Timed("save served scores"):
        # what the replay answered, in the scoring driver's own output
        # format, so served scores can be held against batch scores
        import numpy as np

        from photon_ml_tpu.io.model_io import write_scores

        write_scores(
            os.path.join(output_dir, "scores"),
            np.concatenate(served),
            records_per_file=1 << 20,
            uids=np.concatenate(
                [np.asarray(r.unique_ids) for r in requests]
            ),
        )

    latency = serving_counters.latency_summary()
    summary = {
        "num_requests": len(requests),
        "num_rows": total_rows,
        "request_rows": request_rows,
        "microbatch_shapes": list(shapes),
        "max_wait_ms": max_wait_ms,
        "bf16": bf16,
        "scores_per_sec": batched_rate,
        "scores_per_sec_unbatched": unbatched_rate,
        "latency_ms_p50": latency["p50"],
        "latency_ms_p95": latency["p95"],
        "pad_fraction": serving_counters.pad_fraction(),
        "compiled_signatures": len(scorer.signatures),
        "warm_compiles": warm_compiles.count,
        "replay_compiles": replay_compiles.count,
        # mid-replay hot-swap evidence (None without --swap-model-dir):
        # every submitted request resolved above, so zero were dropped
        "swap": swap_info,
        # per-label compile accounting from the program ledger (None when
        # --telemetry-dir is off): the count's attribution lives in the
        # journal's program_compile/program_recompile rows, phase-stamped
        "program_compiles": None if ledger is None else ledger.snapshot(),
        "runtime": runtime_stamp(),
        "decode_paths": {"requests": part.result.decode_path},
    }
    with open(os.path.join(output_dir, "serving-summary.json"), "w") as f:
        from photon_ml_tpu.cli.game_training_driver import _json_safe

        json.dump(_json_safe(summary), f, indent=2, default=float)
    return summary


def build_arg_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(prog="serve_driver")
    p.add_argument("--requests-avro", required=True,
                   help="Avro scoring records replayed as requests")
    p.add_argument("--model-input-dir", required=True)
    p.add_argument("--output-dir", required=True)
    p.add_argument("--feature-shard-configurations", action="append",
                   default=None)
    p.add_argument("--index-maps-dir")
    p.add_argument("--input-format", default="avro",
                   choices=["avro", "libsvm"])
    p.add_argument("--compact-random-effect-threshold", type=int,
                   default=DEFAULT_COMPACT_RE_THRESHOLD)
    p.add_argument("--microbatch-shapes", default=DEFAULT_SHAPES,
                   help="comma-separated power-of-two micro-batch row "
                        "buckets — the bound on compiled score-program "
                        "signatures")
    p.add_argument("--max-wait-ms", type=float, default=2.0,
                   help="flush deadline: a request waits at most this long "
                        "for batch company before dispatch")
    p.add_argument("--queue-depth", type=int, default=1024,
                   help="bounded request-queue depth (backpressure "
                        "surfaces as a typed submit timeout)")
    p.add_argument("--request-rows", type=int, default=1,
                   help="rows per replayed request")
    p.add_argument("--num-requests", type=int, default=None,
                   help="cap the replay length (default: the whole file)")
    p.add_argument("--bf16", action="store_true",
                   help="whole-path bf16 features+params (not bitwise)")
    p.add_argument("--skip-unbatched-baseline", action="store_true",
                   help="skip the embedded one-request-per-dispatch "
                        "baseline pass")
    p.add_argument("--swap-model-dir",
                   help="hot-swap this refreshed model in-place mid-replay "
                        "(zero-downtime refresh rehearsal; same-layout "
                        "models only — the guard rejects layout changes "
                        "typed)")
    p.add_argument("--swap-at-request", type=int, default=None,
                   help="submit index the swap fires before (default: "
                        "halfway through the replay)")
    p.add_argument("--swap-poll-ms", type=float, default=0.0,
                   help="poll --swap-model-dir every this many ms for "
                        "atomically-renamed model subdirectories and "
                        "hot-swap each continuously through the guarded "
                        "swap API (rejected swaps journal typed and keep "
                        "serving); 0 = the one rehearsed mid-replay swap")
    p.add_argument("--telemetry-dir",
                   help="write a rank-0 JSONL run journal (serve/* "
                        "counters, latency histogram, phase timings) here "
                        "— on the failure path too")
    p.add_argument("--trace-dir",
                   help="write Chrome-trace span timelines here (serve/ "
                        "spans observe the loop; open in Perfetto): the "
                        "device-free timeline; a jax.profiler session "
                        "holds the same spans beside the device")
    return p


def main(argv: Sequence[str] | None = None) -> dict:
    logging.basicConfig(level=logging.INFO)
    from photon_ml_tpu.util.compile_cache import configure_compile_cache

    configure_compile_cache()
    args = build_arg_parser().parse_args(argv)
    shards = None
    if args.feature_shard_configurations:
        shards = dict(
            parse_feature_shard_config(s)
            for s in args.feature_shard_configurations
        )
    return run(
        requests_avro=args.requests_avro,
        model_input_dir=args.model_input_dir,
        output_dir=args.output_dir,
        feature_shards=shards,
        index_maps_dir=args.index_maps_dir,
        input_format=args.input_format,
        compact_random_effect_threshold=args.compact_random_effect_threshold,
        microbatch_shapes=args.microbatch_shapes,
        max_wait_ms=args.max_wait_ms,
        queue_depth=args.queue_depth,
        request_rows=args.request_rows,
        num_requests=args.num_requests,
        bf16=args.bf16,
        skip_unbatched_baseline=args.skip_unbatched_baseline,
        swap_model_dir=args.swap_model_dir,
        swap_at_request=args.swap_at_request,
        swap_poll_ms=args.swap_poll_ms,
        telemetry_dir=args.telemetry_dir,
        trace_dir=args.trace_dir,
    )


if __name__ == "__main__":
    main()
