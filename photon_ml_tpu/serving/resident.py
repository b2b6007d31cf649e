"""Resident scorer: one pre-placed model, a bounded set of compiled
micro-batch score programs.

Reference parity: photon-api transformers/GameTransformer.scala:156-203 —
the reference's scoring is a per-partition batch task that rebuilds its
scorer every job. Here the model placement half of that work is hoisted
out of the request path entirely: a :class:`ResidentScorer` builds and
places the GameModel's device params ONCE (FE coefficient vectors, compact
``[E, K]`` RE tables, MF factors — ``DistributedScorer``'s separable
``params_for_layouts`` half) and keeps them resident across calls, the
Snap ML pre-placed-buffer discipline (arXiv:1803.06333). Each request then
pays only dataset assembly + one dispatch of an already-compiled program.

Why shape buckets: XLA compiles one program per input-shape signature, and
a fresh compile costs seconds where a dispatch of a compiled program costs
far less — an online scorer that compiles per request size would miss
every latency SLO it has. Requests therefore pad into a
SMALL FIXED SET of power-of-two micro-batch shapes (the lane-scheduler
trick reapplied: bounded jit-signature set; pads carry weight 0 /
entity-index −1 / zero feature rows, so they are inert — the framework
padding contract), and sparse entry axes pad to power-of-two lengths the
same way. A request larger than the biggest bucket SPLITS across
micro-batches instead of compiling a new signature.

The whole serving step is ONE traced program end to end (the DrJAX
argument, arXiv:2403.07128): params and the micro-batch both enter the jit
as ARGUMENTS — never closure constants (a closed-over model is baked into
the program: a hot swap would be a recompile; lint check 9 covers this
package) — with the micro-batch buffers DONATED
so steady-state serving reuses device memory instead of allocating per
request. The opt-in bf16 path casts feature blocks AND model params, the
whole path, because a mixed-dtype matmul silently upcasts (the measured
no-op-bf16 landmine).
"""

from __future__ import annotations

import bisect

import numpy as np

from photon_ml_tpu.data.game_data import (
    GameDataset,
    concat_game_datasets,
    pad_game_dataset_to,
    slice_game_dataset,
)
from photon_ml_tpu.models.game import GameModel
from photon_ml_tpu.parallel.scoring import DistributedScorer, _pad_nnz
from photon_ml_tpu.telemetry import program_ledger, serving_counters, tracing
from photon_ml_tpu.telemetry.program_ledger import ledger_jit

#: default micro-batch shape buckets (rows); requests pad to the smallest
#: bucket that fits and split across the largest when they exceed it
DEFAULT_MICROBATCH_SHAPES = (64, 256, 1024)

#: floor for the power-of-two padding of sparse entry axes — tiny requests
#: share one signature instead of minting one per nnz
MIN_NNZ_BUCKET = 64


def _next_pow2(n: int) -> int:
    return 1 << max(0, int(n - 1).bit_length())


class ModelSwapError(ValueError):
    """A hot swap was rejected by the layout fingerprint guard: the new
    model's params layout differs from the resident one's (the message
    names the differing leaves). The resident model keeps serving — a
    layout change needs a fresh scorer (and a warm-up), never an in-place
    swap."""


class ResidentScorer:
    """A GameModel resident on device behind a bounded set of compiled
    micro-batch score programs.

    shapes: the micro-batch shape buckets (positive powers of two,
    ascending); with a mesh each must divide the mesh "data" axis.
    bf16: opt-in whole-path bf16 features+params (NOT bitwise; the default
    f32 path is pinned bitwise against ``DistributedScorer.score_dataset``).
    donate: donate the micro-batch input buffers to the program (None =
    auto: on for real accelerators, off for the CPU backend where XLA
    cannot use them and warns per call).
    """

    def __init__(
        self,
        model: GameModel,
        *,
        shapes=DEFAULT_MICROBATCH_SHAPES,
        mesh=None,
        fe_feature_sharded: "bool | str" = False,
        bf16: bool = False,
        donate: bool | None = None,
    ):
        import jax

        shapes = tuple(int(s) for s in shapes)
        if not shapes:
            raise ValueError("shapes must name at least one micro-batch size")
        for s in shapes:
            if s <= 0 or s & (s - 1):
                raise ValueError(
                    f"micro-batch shape {s} is not a positive power of two — "
                    "the bucket set bounds the compiled-signature count only "
                    "when shapes come from a fixed geometric ladder"
                )
        if sorted(set(shapes)) != list(shapes):
            raise ValueError(f"shapes must be ascending and unique: {shapes}")
        if jax.process_count() > 1:
            raise ValueError(
                "ResidentScorer is the single-process serving path; "
                "multi-process batch scoring goes through "
                "DistributedScorer.score_partitioned"
            )
        self._scorer = DistributedScorer(
            model, mesh, fe_feature_sharded=fe_feature_sharded
        )
        if mesh is not None:
            data_axis = int(mesh.shape["data"])
            for s in shapes:
                if s % data_axis:
                    raise ValueError(
                        f"micro-batch shape {s} does not divide the mesh "
                        f"data axis {data_axis}"
                    )
        self.model = model
        self.shapes = shapes
        self.bf16 = bool(bf16)
        if donate is None:
            donate = jax.default_backend() != "cpu"
        self.donate = bool(donate)
        # Reviewed jit site (lint check 9 allowlist): BOTH operands —
        # the micro-batch data AND the pre-placed model params — enter the
        # program as ARGUMENTS; nothing request- or model-sized is closed
        # over. donate_argnums=(0,) donates only the per-request data
        # buffers; params survive every call (they are the resident state).
        # The program carries the "serve/score" ledger label (ISSUE 13):
        # with a ProgramLedger installed, every serving compile — warm or,
        # pathologically, mid-replay — journals its signature and
        # recompile attribution under that label. The non-donate path
        # therefore owns its program instead of aliasing the batch
        # scorer's (serving compiles must not hide under
        # score/score_dataset); the jit caches only coincided when a
        # micro-batch signature exactly matched a prior full-dataset
        # score, so the bound stays the bucket set either way.
        self._program = ledger_jit(
            self._scorer._score_impl, label="serve/score",
            donate_argnums=(0,) if self.donate else (),
        )
        self._bf16_params_cache: dict = {}
        #: bumped by swap_model: bf16 cache keys carry it, so entries a
        #: racing reader computes from a superseded model are never read
        self._model_version = 0
        self._signatures: set = set()

    # -- program inputs ------------------------------------------------------

    @property
    def signatures(self) -> "frozenset":
        """(bucket, layout, nnz-bucket) signatures scored so far — bounded
        by the configured shape set times the model's (fixed) layout."""
        return frozenset(self._signatures)

    def _bucket_for(self, n: int) -> int:
        i = bisect.bisect_left([s for s in self.shapes], n)
        return self.shapes[min(i, len(self.shapes) - 1)]

    def _cast_bf16(self, tree):
        import jax
        import jax.numpy as jnp

        def cast(leaf):
            if hasattr(leaf, "dtype") and jnp.issubdtype(
                jnp.asarray(leaf).dtype, jnp.floating
            ):
                return jnp.asarray(leaf, jnp.bfloat16)
            return leaf

        return jax.tree_util.tree_map(cast, tree)

    def _params(self, layouts):
        # version read BEFORE the params fetch: a swap committing in
        # between bumps the version, so whatever this thread caches below
        # lands under the superseded key and is never read again (the
        # other order would cache OLD params under the NEW version)
        version = self._model_version
        params = self._scorer.params_for_layouts(layouts)
        if not self.bf16:
            return params
        key = (version, tuple(sorted(layouts.items())))
        cached = self._bf16_params_cache.get(key)
        if cached is None:
            cached = self._bf16_params_cache[key] = self._cast_bf16(params)
        return cached

    def _pad_entry_axes(self, data, xp) -> tuple:
        """Pad every flat entry axis (sparse FE triples, compact-RE entry
        lists) to a power-of-two length so the nnz axis joins the bounded
        signature set; pads are inert (value 0, repeated last row id, the
        compact scratch slot). Returns (data, nnz signature tuple)."""
        mesh = self._scorer.mesh
        data_axis = int(mesh.shape["data"]) if mesh is not None else 1
        nnz_sig = []
        for cid, c in data["coords"].items():
            if "sparse" in c:
                nnz = int(np.shape(c["sparse"]["vals"])[0])
                target = max(_next_pow2(max(nnz, 1)), MIN_NNZ_BUCKET,
                             data_axis)
                c["sparse"] = _pad_nnz(
                    dict(c["sparse"]), data_axis, xp=xp, target=target
                )
                nnz_sig.append((cid, target))
            if "entries" in c:
                nnz = int(np.shape(c["entries"]["vals"])[0])
                target = max(_next_pow2(max(nnz, 1)), MIN_NNZ_BUCKET,
                             data_axis)
                k_scratch = int(
                    self.model.models[cid].coefficients.shape[1]
                )
                c["entries"] = _pad_nnz(
                    dict(c["entries"]), data_axis, xp=xp, target=target,
                    pad_values={"pos": k_scratch},
                )
                nnz_sig.append((cid, target))
        return data, tuple(nnz_sig)

    # -- zero-downtime model refresh ----------------------------------------

    def swap_model(self, new_model: GameModel) -> None:
        """In-place hot swap to a refreshed model while requests keep
        flowing — the serving half of incremental retraining
        (algorithm/refresh.py). Params are jit ARGUMENTS keyed by layout,
        so an EQUAL-layout swap re-uses every compiled score program
        (``xla/serve/score`` compile delta == 0, ledger-pinned by
        tests/test_serving.py); a layout-changing model raises
        :class:`ModelSwapError` naming the differing leaves BEFORE any
        state mutates, and the resident model keeps serving.

        This method is the ONE sanctioned resident-param mutation site in
        the serving package (dev/lint_parity.py check 14): the new params
        are built and placed fully off to the side, then committed by
        reference assignment (atomic under the GIL), so a concurrent
        micro-batch scores either the old or the new model — never a mix.
        """
        try:
            # the layout fingerprint guard lives in the ONE inner API
            # (parallel/scoring.py swap_model_params): validate-then-
            # commit, nothing mutates on rejection. It also rebuilds +
            # re-places the layout-keyed params cache and re-feeds
            # serve/resident_params_bytes (the HBM-forecast input).
            self._scorer.swap_model_params(new_model)
        except ValueError as e:
            serving_counters.record_swap_rejected()
            raise ModelSwapError(
                f"model swap rejected: {e} — build a fresh ResidentScorer "
                "(and warm it) for a layout-changing refresh"
            ) from e
        self.model = new_model
        # version-keyed bf16 cache: a scorer thread racing the swap may
        # still INSERT an entry computed from the old model after this
        # reset — the version bump makes stale entries unreachable
        # instead of served
        self._model_version += 1
        self._bf16_params_cache = {}
        serving_counters.record_model_swap()
        ledger = program_ledger.current_ledger()
        if ledger is not None:
            # no compile fires on an equal-layout swap, so the per-label
            # HBM forecast must be re-fed by hand or it keeps pricing the
            # stale model's resident bytes (ISSUE 13 accounting)
            ledger.refeed_resident_forecast("serve/score")

    # -- scoring -------------------------------------------------------------

    def score(self, dataset: GameDataset) -> np.ndarray:
        """[n] host scores INCLUDING offsets (``score_dataset`` semantics)
        for one request, through the bucketed resident program. Requests
        larger than the biggest bucket split across micro-batches (never a
        fresh compile)."""
        n = dataset.num_samples
        if n == 0:
            return np.zeros((0,), np.float32)
        max_shape = self.shapes[-1]
        if n > max_shape:
            serving_counters.record_bucket_split()
            parts = [
                self._score_bucketed(slice_game_dataset(dataset, lo,
                                                        min(lo + max_shape, n)))
                for lo in range(0, n, max_shape)
            ]
            return np.concatenate(parts)
        return self._score_bucketed(dataset)

    def _score_bucketed(self, dataset: GameDataset) -> np.ndarray:
        import jax.numpy as jnp

        import jax

        n = dataset.num_samples
        bucket = self._bucket_for(n)
        with tracing.span("serve/score", cat="serve", rows=n, bucket=bucket):
            padded, _ = pad_game_dataset_to(dataset, bucket)
            data, layouts = self._scorer._build_data_host(padded, jnp)
            data, nnz_sig = self._pad_entry_axes(data, jnp)
            if self.donate and padded is dataset:
                # pad == 0: the built data aliases the request dataset's
                # own device arrays (jnp.asarray no-ops), and donating
                # them would delete the caller's buffers — a later score
                # of the same dataset (warm-up reuse, per-request
                # isolation retry) would hit 'Array has been deleted'.
                # Padded requests build fresh host arrays, so only this
                # branch needs the defensive copy.
                data = jax.tree_util.tree_map(
                    lambda a: jnp.array(a, copy=True), data
                )
            if self.bf16:
                # feature blocks only: the whole matmul path runs bf16
                # against the bf16 params (a mixed-dtype matmul would
                # silently upcast); offsets/indices stay as built
                data["coords"] = {
                    cid: {
                        k: (self._cast_bf16(v) if k in ("x", "sparse",
                                                        "entries") else v)
                        for k, v in c.items()
                    }
                    for cid, c in data["coords"].items()
                }
            if self._scorer.mesh is not None:
                data = self._scorer._place_data(data)
            params = self._params(layouts)
            sig = (bucket, tuple(sorted(layouts.items())), nnz_sig)
            self._signatures.add(sig)
            if self._scorer.mesh is not None:
                with self._scorer.mesh:
                    out = self._program(data, params)
            else:
                out = self._program(data, params)
            # the compiled-signature gauge is ledger-backed (ISSUE 13):
            # with a ProgramLedger installed the count comes from the
            # "serve/score" program's observed signature registry; the
            # local (bucket, layout, nnz) set is the fallback — and stays
            # the public ``signatures`` property either way
            ledger = program_ledger.current_ledger()
            ledger_sigs = (
                ledger.signature_count("serve/score")
                if ledger is not None else 0
            )
            serving_counters.set_compiled_signatures(
                ledger_sigs or len(self._signatures)
            )
            scores = np.asarray(out)[:n]
            serving_counters.record_scored(rows=n, padded_rows=bucket - n)
        if scores.dtype != np.float32 and self.bf16:
            scores = scores.astype(np.float32)
        return scores

    def warm(self, example: GameDataset) -> int:
        """Compile every bucket signature up front from an example request
        (rows are recycled as needed) so the first live requests never pay
        a compile; returns the number of signatures now resident."""
        n = example.num_samples
        if n == 0:
            raise ValueError("warm() needs a non-empty example dataset")
        for shape in self.shapes:
            take = min(n, shape)
            part = slice_game_dataset(example, 0, take) if take < n else example
            reps = -(-shape // take)
            if reps > 1:
                part = concat_game_datasets([part] * reps)
                part = slice_game_dataset(part, 0, shape)
            self._score_bucketed(part)
        return len(self._signatures)
