"""Serving-path telemetry: latency-SLO histograms + micro-batch counters.

No reference analogue as code: the reference's scoring is an offline batch
job (photon-client cli/game/scoring/GameScoringDriver.scala) whose only
evidence is Spark task metrics; an online resident scorer lives or dies by
its latency distribution, so the serving layer (photon_ml_tpu/serving/)
feeds the process-wide metrics registry with exactly the SLO evidence an
operator needs: per-request latency p50/p95 (``time.perf_counter`` spans —
lint check 11), queue depth, request/batch/row counts, and the pad
fraction the shape-bucket discipline costs.

Names are constants so producers (serving/resident.py, serving/batching.py)
and consumers (tests, journals, cli/serve_driver.py) cannot
drift — the same contract as telemetry/stream_counters.py.
"""

from __future__ import annotations

from photon_ml_tpu.telemetry.registry import default_registry

#: prefix shared by every serving metric (reset_serving_metrics)
SERVING_METRIC_PREFIX = "serve/"
#: submit-to-result latency per request (ms): the SLO histogram — its
#: p50/p95 are what the serve driver reports
LATENCY_MS = "serve/latency_ms"
#: bounded request-queue depth observed at each enqueue/dequeue
QUEUE_DEPTH = "serve/queue_depth"
#: requests accepted into the queue
REQUESTS = "serve/requests"
#: device dispatches the micro-batching loop issued (coalesced flushes)
BATCHES = "serve/batches"
#: true rows scored (request rows, pads excluded)
ROWS = "serve/rows"
#: pad rows the shape-bucket discipline added on top of ROWS
PADDED_ROWS = "serve/padded_rows"
#: cumulative padded_rows / (rows + padded_rows) — the bucket-set tax
PAD_FRACTION = "serve/pad_fraction"
#: requests that failed (poisoned input, scoring error) — each one
#: attributed to its request id, never fatal to the serving loop
REQUEST_FAILURES = "serve/request_failures"
#: distinct (shape-bucket, layout) program signatures the resident scorer
#: has scored through — bounded by the configured bucket set, which is the
#: whole point (one compile per signature, zero per-request compiles)
COMPILED_SIGNATURES = "serve/compiled_signatures"
#: over-sized requests split across micro-batches instead of compiling a
#: fresh signature (the bucket-miss rule)
BUCKET_SPLITS = "serve/bucket_splits"
#: bytes of placed model params resident in the layout-keyed cache
#: (parallel/scoring.py params_for_layouts) — the resident half of the
#: program ledger's HBM-overcommit forecast (telemetry/program_ledger.py)
RESIDENT_PARAMS_BYTES = "serve/resident_params_bytes"
#: in-place model refreshes accepted by the guarded swap API
#: (serving/resident.py swap_model — zero recompiles on a same-layout swap)
MODEL_SWAPS = "serve/model_swaps"
#: swaps REJECTED typed by the layout fingerprint guard — the serving loop
#: keeps running on the resident model after each one
SWAP_REJECTED = "serve/swap_rejected"


def reset_serving_metrics(registry=None) -> None:
    """Drop per-run serving metrics — the serve driver calls this at run
    start (next to ``reset_resilience_metrics``) and again between its
    embedded unbatched baseline and the batched replay, so the journal
    snapshot carries only the replay's own latency distribution."""
    reg = registry or default_registry()
    reg.remove_prefix(SERVING_METRIC_PREFIX)


def record_request_latency_ms(ms: float) -> None:
    default_registry().histogram(LATENCY_MS).observe(float(ms))


def set_queue_depth(depth: int) -> None:
    default_registry().gauge(QUEUE_DEPTH).set(int(depth))


def record_request(n: int = 1) -> None:
    default_registry().counter(REQUESTS).inc(int(n))


def record_request_failure(n: int = 1) -> None:
    default_registry().counter(REQUEST_FAILURES).inc(int(n))


def record_batch() -> None:
    default_registry().counter(BATCHES).inc()


def record_scored(rows: int, padded_rows: int) -> None:
    """One scored micro-batch's row accounting; refreshes the cumulative
    pad-fraction gauge."""
    reg = default_registry()
    reg.counter(ROWS).inc(int(rows))
    reg.counter(PADDED_ROWS).inc(int(padded_rows))
    total = reg.counter(ROWS).value + reg.counter(PADDED_ROWS).value
    if total:
        reg.gauge(PAD_FRACTION).set(
            reg.counter(PADDED_ROWS).value / total
        )


def set_compiled_signatures(n: int) -> None:
    default_registry().gauge(COMPILED_SIGNATURES).set(int(n))


def set_resident_params_bytes(n: int) -> None:
    default_registry().gauge(RESIDENT_PARAMS_BYTES).set(int(n))


def record_model_swap() -> None:
    default_registry().counter(MODEL_SWAPS).inc()


def record_swap_rejected() -> None:
    default_registry().counter(SWAP_REJECTED).inc()


def record_bucket_split(n: int = 1) -> None:
    default_registry().counter(BUCKET_SPLITS).inc(int(n))


def latency_summary() -> dict:
    return default_registry().histogram(LATENCY_MS).summary()


def pad_fraction() -> float:
    value = default_registry().gauge(PAD_FRACTION).value
    return float(value or 0.0)
