"""Out-of-core streaming-epoch telemetry: the observable overlap.

Reference parity: the reference's beyond-memory ingestion rode Spark's
per-task input metrics (AvroDataReader.scala work shows up in the task UI
as input bytes/records); here the equivalent evidence for the chunked
streaming pipeline (io/stream_reader.py + algorithm/streaming.py) lives in
the process-wide metrics registry so run journals can prove — on success
AND failure paths — that host decode was actually hidden behind device
compute instead of serialized with it.

Names are constants so the producers (the chunk prefetcher / epoch runner)
and consumers (tests, journals) cannot drift.
"""

from __future__ import annotations

from photon_ml_tpu.telemetry.registry import default_registry

#: per-chunk host decode+assembly duration (ms) — fed by the prefetcher
#: for every chunk it produces, prefetch on or off
CHUNK_DECODE_MS = "io/chunk_decode_ms"
#: prefix shared by the epoch-level gauges (reset_stream_metrics)
STREAM_METRIC_PREFIX = "stream/"
#: fraction of total host decode time hidden behind device compute in the
#: most recent epoch: 1 - (consumer wait / total decode), clamped to
#: [0, 1]; 0.0 when prefetch is off (nothing can hide)
OVERLAP_FRACTION = "stream/overlap_fraction"
#: chunk count of the most recent epoch
CHUNKS_PER_EPOCH = "stream/chunks_per_epoch"
#: streamed-GAME run evidence (algorithm/streaming_game.py): total chunk
#: LOADS (source decodes — DuHL working-set cache hits don't count; the
#: cache is exactly what the schedule saves) and chunk VISITS (schedule
#: entries processed by random-effect solves, loads or hits)
GAME_CHUNK_LOADS = "stream/game_chunk_loads"
GAME_CHUNK_VISITS = "stream/game_chunk_visits"
#: sweeps the most recent streamed-GAME train ran (epochs-to-tolerance
#: evidence for the DuHL-vs-uniform comparison)
GAME_SWEEPS = "stream/game_sweeps"


def reset_stream_metrics(registry=None) -> None:
    """Drop per-run streaming metrics — drivers call this at run start next
    to ``reset_solver_metrics``/``reset_layout_metrics`` so each run's
    journal snapshot (taken on success AND failure paths) carries only its
    own epochs' decode histogram and overlap evidence."""
    reg = registry or default_registry()
    reg.remove_prefix(STREAM_METRIC_PREFIX)
    reg.remove_prefix(CHUNK_DECODE_MS)


def record_chunk_decode_ms(ms: float) -> None:
    default_registry().histogram(CHUNK_DECODE_MS).observe(float(ms))


def set_overlap_fraction(fraction: float) -> None:
    default_registry().gauge(OVERLAP_FRACTION).set(float(fraction))


def set_chunks_per_epoch(n: int) -> None:
    default_registry().gauge(CHUNKS_PER_EPOCH).set(int(n))


def overlap_fraction() -> float:
    value = default_registry().gauge(OVERLAP_FRACTION).value
    return float(value or 0.0)


def chunks_per_epoch() -> int:
    value = default_registry().gauge(CHUNKS_PER_EPOCH).value
    return int(value or 0)


def set_game_stream_evidence(
    *, chunk_loads: int, chunk_visits: int, sweeps: int
) -> None:
    default_registry().gauge(GAME_CHUNK_LOADS).set(int(chunk_loads))
    default_registry().gauge(GAME_CHUNK_VISITS).set(int(chunk_visits))
    default_registry().gauge(GAME_SWEEPS).set(int(sweeps))


def game_stream_evidence() -> dict:
    reg = default_registry()
    return {
        "chunk_loads": int(reg.gauge(GAME_CHUNK_LOADS).value or 0),
        "chunk_visits": int(reg.gauge(GAME_CHUNK_VISITS).value or 0),
        "sweeps": int(reg.gauge(GAME_SWEEPS).value or 0),
    }


def chunk_decode_summary() -> dict:
    return default_registry().histogram(CHUNK_DECODE_MS).summary()
