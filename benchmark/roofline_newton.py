"""Bytes a Newton round's Hessian pass has to move over the random effects'
lanes, and the share of the HBM roofline the measured seconds under
``newton/hessian`` reach.

The count is the ALGORITHM's, not an implementation's: a round of a bucket
needs ``X_e' D X_e`` for every lane of its ``[e, cap, d]`` block, which cannot
be made without reading the block once and writing the ``[e, d, d]`` result,
and need not move more (the weights ``d2`` are a function of margins the round
already holds; for a squared loss they are ones). An implementation that
materializes ``D X`` beside ``X``, pads ``d`` to a lane tile or re-lays the
block out shows as a smaller share; the yardstick does not move when the
implementation does (``benchmark/roofline_hv.py``'s words). The arithmetic,
``2 e cap d^2`` flops, is far under the chip's rate at ``d`` = 16.

Rounds: the program counts a sweep's LOCK-STEP rounds summed over its bucket
solves (``solver/newton_lockstep_rounds``: every lane of a bucket is read
until the bucket's slowest lane has stopped), not bucket by bucket, so a
bucket solve is given the MEAN over the bucket solves. Exact where every
bucket runs the same rounds (two, for ridge lanes at the float's floor); a
floor where larger buckets run no fewer rounds than smaller ones, which is
what lock-step gives (more lanes, a later last lane).
"""

from __future__ import annotations

from benchmark.peaks import peaks_for


def hessian_bytes(lanes: int, cap: int, d: int, itemsize: int) -> int:
    """One round of one bucket: its block once, the [lanes, d, d] float32
    result out."""
    return lanes * cap * d * itemsize + lanes * d * d * 4


def newton_hessian_roofline_pct(rounds_a_bucket_solve: float, buckets, sweeps: int,
                                hessian_seconds: float, device_kind: str) -> float:
    """Least time the chip could take for ``sweeps`` sweeps' Hessian passes
    (``buckets``: (lanes, cap, d, itemsize) of every bucket a sweep solves,
    each ``rounds_a_bucket_solve`` times) over the measured seconds, in
    percent. Never clipped: a reading over 100 means time is missing, and has
    to show."""
    a_round = sum(hessian_bytes(*bucket) for bucket in buckets)
    least = sweeps * rounds_a_bucket_solve * a_round / peaks_for(
        device_kind)["hbm_bytes_per_s"]
    return 100.0 * least / hessian_seconds
