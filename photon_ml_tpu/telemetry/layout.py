"""Layout-decision observability for the sparse batch builders.

Reference parity: no reference analogue — the Spark reference never chooses
a device layout (its sparse vectors stay Breeze CSR end to end); this is
TPU-first observability for the hybrid dense-head / sparse-tail builder
(data/sparse_batch.py, ISSUE 5). The hot-coverage fraction, head width
k_hot, and the cold tail's slots, width tiers and padding share are exactly
the quantities that decide whether the layout wins (the expected win is
index-op removal proportional to hot coverage, BASELINE.md r6), so they are
recorded as registry gauges the run journal persists on success AND failure
paths (both drivers snapshot the registry in their ``finally`` blocks).

Per-run lifecycle mirrors ``solver/*``: drivers call
:func:`reset_layout_metrics` at run start (next to ``reset_solver_metrics``)
so repeated ``run()`` calls journal per-run decisions, not stale ones.

No jax dependency — importable before the backend is chosen.
"""

from __future__ import annotations

#: registry namespace for layout-decision metrics
LAYOUT_METRIC_PREFIX = "layout/"


def reset_layout_metrics(registry=None) -> None:
    """Drop per-run layout/* gauges and counters — drivers call this at run
    start so each run's journal carries its own layout decisions."""
    from photon_ml_tpu.telemetry.registry import default_registry

    reg = registry or default_registry()
    reg.remove_prefix(LAYOUT_METRIC_PREFIX)


def record_hybrid_layout(
    label: str,
    *,
    k_hot: int,
    k_hot_padded: int,
    hot_coverage: float,
    hot_nnz: int,
    tail_nnz: int,
    registry=None,
) -> None:
    """One hybrid split's decision (``data/sparse_batch._hybrid_arrays``),
    as gauges under ``layout/<label>/*`` plus a ``layout/<label>/builds``
    counter: the head's columns and coverage and the entries left to the
    cold tail (``tail_nnz``: entries, padding apart)."""
    from photon_ml_tpu.telemetry.registry import default_registry

    reg = registry or default_registry()
    base = f"{LAYOUT_METRIC_PREFIX}{label}"
    reg.counter(f"{base}/builds").inc()
    _set_gauges(reg, base, (
        ("k_hot", k_hot),
        ("k_hot_padded", k_hot_padded),
        ("hot_coverage", hot_coverage),
        ("hot_nnz", hot_nnz),
        ("tail_nnz", tail_nnz),
    ))


def record_tail_layout(
    label: str,
    *,
    tail_slots: int,
    tail_tiers: int,
    tail_width: int,
    tail_pad_share: float,
    hybrid_bytes: int,
    registry=None,
) -> None:
    """What a hybrid batch's builder made of the cold tail, counted on the
    arrays that were BUILT: ``tail_slots`` = the slots an evaluation gathers
    and scatters (every ELL tier's, pads included, plus the flat overflow's
    length), ``tail_tiers`` = the ELL view's width tiers (0: a flat tail),
    ``tail_width`` = the view's last width, ``tail_pad_share`` = the share of
    the slots that holds no entry, ``hybrid_bytes`` = the bytes of the head
    and the tail's arrays together."""
    from photon_ml_tpu.telemetry.registry import default_registry

    reg = registry or default_registry()
    _set_gauges(reg, f"{LAYOUT_METRIC_PREFIX}{label}", (
        ("tail_slots", tail_slots),
        ("tail_tiers", tail_tiers),
        ("tail_width", tail_width),
        ("tail_pad_share", tail_pad_share),
        ("hybrid_bytes", hybrid_bytes),
    ))


def record_global_hot_ranking(
    label: str,
    *,
    k_hot: int,
    global_nnz: int,
    num_ranks: int,
    registry=None,
) -> None:
    """One partitioned-ingest GLOBAL hot-column resolution
    (io/partitioned_reader.py): the head was elected from the summed
    per-rank nnz histograms, not this rank's local block — the gauge trio
    is the journal evidence that a composed hybrid x --partitioned-io run
    ranked globally (every rank records identical values)."""
    from photon_ml_tpu.telemetry.registry import default_registry

    reg = registry or default_registry()
    base = f"{LAYOUT_METRIC_PREFIX}{label}"
    reg.counter(f"{base}/global_hot_rankings").inc()
    _set_gauges(reg, base, (
        ("global_hot_k", k_hot),
        ("global_hot_nnz", global_nnz),
        ("global_hot_ranks", num_ranks),
    ))


def record_block_head(
    label: str,
    *,
    width: int,
    num_blocks: int,
    k_hot_padded: int,
    registry=None,
) -> None:
    """The column-sharded builder's per-block head shape: every block pads
    to the WIDEST block's hot count, so hot ids clustered into few
    contiguous column blocks inflate ``width·num_blocks`` well past the
    global head size — ``block_head_replication`` is that blow-up factor
    (1.0 = perfectly spread head; ~num_blocks = fully clustered, the
    degenerate regime the builder also warns about)."""
    from photon_ml_tpu.telemetry.registry import default_registry

    reg = registry or default_registry()
    base = f"{LAYOUT_METRIC_PREFIX}{label}"
    _set_gauges(reg, base, (
        ("block_head_width", width),
        ("block_head_replication",
         width * num_blocks / k_hot_padded if k_hot_padded else 0.0),
    ))


def _set_gauges(reg, base: str, pairs) -> None:
    for name, value in pairs:
        reg.gauge(f"{base}/{name}").set(value)
