"""GAME scoring driver: load a model, score a dataset, save scores.

Reference parity: photon-client cli/game/scoring/GameScoringDriver.scala —
run() (:133-194): prepare feature maps, read data, load GAME model from the
training output layout, GameTransformer.transform, optional evaluation,
saveScoresToHDFS (:191-253, ScoringResultAvro records).
"""

from __future__ import annotations

import argparse
import json
import logging
import os
from typing import Sequence

import numpy as np

from photon_ml_tpu.cli.configs import (
    evaluation_id_columns,
    parse_feature_shard_config,
)
from photon_ml_tpu.cli.game_training_driver import _parse_mesh_shape
from photon_ml_tpu.io.index_map import IndexMap
from photon_ml_tpu.io.partitioned_reader import read_partitioned
from photon_ml_tpu.io.model_io import DEFAULT_COMPACT_RE_THRESHOLD, load_game_model, write_scores
from photon_ml_tpu.models.game import RandomEffectModel
from photon_ml_tpu.models.matrix_factorization import MatrixFactorizationModel
from photon_ml_tpu.transformers import GameTransformer
from photon_ml_tpu.util import Timed

logger = logging.getLogger(__name__)


def run(
    *,
    input_data_path: "str | Sequence[str]",
    model_input_dir: str,
    output_dir: str,
    feature_shards: dict | None = None,
    index_maps_dir: str | None = None,
    evaluators: Sequence[str] = (),
    model_id: str = "",
    input_format: str = "avro",
    compact_random_effect_threshold: int = DEFAULT_COMPACT_RE_THRESHOLD,
    distributed: bool = False,
    mesh_shape: dict | None = None,
    fe_feature_sharded: bool = False,
    partitioned_io: bool = False,
    on_corrupt: str = "raise",
    telemetry_dir: str | None = None,
    trace_dir: str | None = None,
) -> dict:
    """Score ``input_data_path`` with the model at ``model_input_dir``.

    input_data_path: one dataset path, or a sequence of paths scored in
    one run — the model Avro is parsed and its device placement built
    ONCE (the separable-placement API: ``DistributedScorer.
    params_for_layouts`` caches the placed model across datasets), each
    dataset writing under ``output_dir/dataset-NNNN``. A single path keeps
    the historical single-dataset output layout exactly.

    on_corrupt: "raise" (strict, default) or "quarantine" — skip-and-count
    corrupt Avro container blocks during ingestion (io/avro.py); spans and
    the resilience/* counters land in the run journal.

    telemetry_dir: rank-0 JSONL run journal (phase timings, io/resilience
    counters) — written on the FAILURE path too, so a scoring run that
    died mid-read still leaves its retry/quarantine evidence.

    trace_dir: per-rank Chrome-trace span timelines
    (``trace-{rank:05d}.json``; telemetry/tracing.py) + a rank-merged
    straggler report journaled at run end — flushed on success AND
    failure paths, before the failure journal rows.

    Index maps default to the ones the training driver saved next to the
    model (<root>/index-maps); feature shard configs default to one shard
    per saved index map using the bag of the same name.

    distributed/mesh_shape: score through the jitted mesh-sharded SPMD
    program (parallel/scoring.DistributedScorer) over a ("data", "model")
    mesh — the analogue of the reference's executor-distributed scoring
    (GameTransformer.scala:156-203). fe_feature_sharded additionally
    shards the FE coordinate's feature/coefficient axis over "model"
    (mesh model>1 implies it), so column-sharded giant-d models score
    without replicating the coefficient vector.

    partitioned_io: multi-process runs decode only ~1/P of the input per
    rank (io/partitioned_reader.py) and every rank writes its OWN
    part-NNNNN.avro score shard into the shared output directory
    (io/score_writer.ShardedScoreWriter — the reference's per-partition
    ScoreProcessingUtils layout), replacing the process_allgather score
    funnel. ``output_dir`` is then one SHARED directory; evaluators are
    not supported on this path yet. Single-process runs are unaffected.
    """
    import jax

    if on_corrupt not in ("raise", "quarantine"):
        raise ValueError(
            f"on_corrupt must be 'raise' or 'quarantine', got {on_corrupt!r}"
        )
    partitioned = partitioned_io and jax.process_count() > 1
    if partitioned and not (distributed or mesh_shape):
        raise ValueError(
            "--partitioned-io requires --distributed or --mesh (the "
            "partitioned blocks feed a mesh's addressable shards)"
        )
    # hybrid x --partitioned-io composes since ISSUE 6: the partitioned
    # reader resolves one GLOBAL hot head over the metadata exchange, so
    # every rank's layout agrees (io/partitioned_reader.py); scores are
    # layout-independent either way.
    from photon_ml_tpu.telemetry import RunJournal
    from photon_ml_tpu.telemetry.resilience_counters import (
        reset_resilience_metrics,
    )
    from photon_ml_tpu.util.timed import reset_timings, timing_summary

    reset_timings()
    reset_resilience_metrics()
    journal = RunJournal(telemetry_dir) if telemetry_dir else None
    # program ledger rides --telemetry-dir (ISSUE 13): the scoring program
    # (score/score_dataset) journals its compile/cost/signature accounting
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
    exchange = None
    coordinator = None
    if partitioned:
        from photon_ml_tpu.parallel.multihost import default_exchange
        from photon_ml_tpu.resilience import CoordinatedRecovery

        exchange = default_exchange()
        # scoring has no restart loop, but the coordinator still buys
        # ATTRIBUTION (ISSUE 15): the run's exchange is generation-fenced,
        # and a rank dying of a classified-transient failure posts an
        # abort marker below, so its peers fail fast with a PeerAbort
        # naming it instead of burning the full exchange deadline
        coordinator = CoordinatedRecovery(
            exchange, max_restarts=0, journal=journal,
            description="partitioned scoring",
        )
    succeeded = False
    try:
        summary = _run_inner(
            input_data_path=input_data_path,
            model_input_dir=model_input_dir,
            output_dir=output_dir,
            feature_shards=feature_shards,
            index_maps_dir=index_maps_dir,
            evaluators=evaluators,
            model_id=model_id,
            input_format=input_format,
            compact_random_effect_threshold=compact_random_effect_threshold,
            distributed=distributed,
            mesh_shape=mesh_shape,
            fe_feature_sharded=fe_feature_sharded,
            partitioned=partitioned,
            on_corrupt=on_corrupt,
            journal=journal,
            exchange=exchange,
        )
        succeeded = True
        if journal is not None:
            journal.record("scoring_summary", **summary)
        return summary
    except Exception as e:  # attributed, then re-raised — never swallowed
        from photon_ml_tpu.resilience import is_transient

        if coordinator is not None and is_transient(e):
            coordinator.post_abort(e)
        raise
    finally:
        # traces flush FIRST (before the failure journal rows) so a dead
        # run still leaves a readable per-rank timeline; the straggler
        # merge + barriered publish run collectives only on the success
        # path (every rank's run() reaches this finally)
        if tracer is not None:
            from photon_ml_tpu.parallel.multihost import default_exchange
            from photon_ml_tpu.telemetry.tracing import (
                flush_trace_best_effort,
                uninstall_tracer,
            )

            try:
                # best-effort: a publication error never masks the run's
                # own outcome or skips the journal rows below. The run's
                # (possibly fenced) exchange is reused so the merge rides
                # the same key namespace as the run itself.
                flush_trace_best_effort(
                    tracer, trace_dir,
                    exchange=(
                        (exchange or default_exchange()) if succeeded
                        else None
                    ),
                    gather=succeeded,
                    journal=journal,
                )
            finally:
                uninstall_tracer()
        if ledger is not None:
            from photon_ml_tpu.telemetry.program_ledger import uninstall_ledger

            uninstall_ledger()
        # failure-path journaling too: the resilience/* counters (retries,
        # giveups, quarantined_blocks) and quarantine spans are exactly
        # what a post-mortem of a dead scoring run needs
        if journal is not None:
            from photon_ml_tpu.telemetry import (
                default_registry,
                resilience_counters,
            )

            for event in resilience_counters.drain_quarantine_events():
                journal.record("quarantined_block", **event)
            journal.record_timings(timing_summary())
            journal.record_metrics(default_registry().snapshot())
            journal.close()


def _load_scoring_model(
    *,
    model_input_dir: str,
    index_maps_dir: str | None,
    feature_shards: dict | None,
    compact_random_effect_threshold: int,
):
    """Parse the model Avro + index maps ONCE: (model, index_maps,
    feature_shards, entity_vocabs, re_columns). Hoisted out of the
    per-dataset scoring loop (and reused by cli/serve_driver.py) so a run
    that scores several datasets — or serves requests — never re-parses
    the model."""
    if index_maps_dir is None:
        candidate = os.path.join(os.path.dirname(model_input_dir.rstrip("/")), "index-maps")
        index_maps_dir = candidate if os.path.isdir(candidate) else None
    # both formats: plain .keys and native off-heap .photonix stores
    index_maps = IndexMap.load_directory(index_maps_dir) if index_maps_dir else {}
    if index_maps:
        if feature_shards is None:
            # shard name == bag name is OUR training driver's convention,
            # only trustworthy for maps its stores produced
            from photon_ml_tpu.io.data_reader import FeatureShardConfiguration

            feature_shards = {
                shard: FeatureShardConfiguration(feature_bags=(shard, "features"))
                for shard in index_maps
            }
        with Timed("load model"):
            model = load_game_model(
                model_input_dir, index_maps,
                compact_random_effect_threshold=compact_random_effect_threshold,
            )
    else:
        # no saved stores (e.g. a reference-written model whose index maps
        # are JVM-only PalDB): one pass rebuilds maps from the model's own
        # records while loading. Shard->bag mapping cannot be guessed for a
        # foreign model, so explicit shard configs are required.
        if feature_shards is None:
            raise ValueError(
                "no saved index-map stores next to this model: pass "
                "--feature-shard-configurations mapping each model shard id "
                "to the data's feature bags"
            )
        from photon_ml_tpu.io.model_io import load_game_model_and_index_maps

        logger.info("no index-map stores found; rebuilding from model records")
        with Timed("load model"):
            model, index_maps = load_game_model_and_index_maps(
                model_input_dir,
                compact_random_effect_threshold=compact_random_effect_threshold,
            )
    entity_vocabs: dict[str, np.ndarray] = {}

    def set_vocab(effect_type: str, keys: np.ndarray) -> None:
        keys = np.asarray(keys)
        existing = entity_vocabs.get(effect_type)
        if existing is not None and not np.array_equal(existing, keys):
            # two sub-models disagreeing on a shared entity space would
            # silently misalign one model's table rows
            raise ValueError(
                f"sub-models disagree on entity keys for effect type "
                f"'{effect_type}' ({len(existing)} vs {len(keys)} keys); "
                "cannot build a consistent scoring vocab"
            )
        entity_vocabs[effect_type] = keys

    for m in model.models.values():
        if isinstance(m, RandomEffectModel):
            set_vocab(m.random_effect_type, m.entity_keys)
        elif isinstance(m, MatrixFactorizationModel):
            set_vocab(m.row_effect_type, m.row_keys)
            set_vocab(m.col_effect_type, m.col_keys)
    re_columns = tuple(sorted(entity_vocabs))
    return model, index_maps, feature_shards, entity_vocabs, re_columns


def _run_inner(
    *,
    input_data_path: "str | Sequence[str]",
    model_input_dir: str,
    output_dir: str,
    feature_shards: dict | None,
    index_maps_dir: str | None,
    evaluators: Sequence[str],
    model_id: str,
    input_format: str,
    compact_random_effect_threshold: int,
    distributed: bool,
    mesh_shape: dict | None,
    fe_feature_sharded: bool,
    partitioned: bool,
    on_corrupt: str,
    journal=None,
    exchange=None,
) -> dict:
    import jax
    if partitioned and evaluators:
        raise ValueError(
            "--partitioned-io does not support --evaluators yet; evaluate "
            "through the non-partitioned scoring path"
        )
    from photon_ml_tpu.parallel.multihost import default_exchange
    from photon_ml_tpu.telemetry.probes import runtime_stamp

    paths = (
        [input_data_path] if isinstance(input_data_path, (str, os.PathLike))
        else list(input_data_path)
    )
    if not paths:
        raise ValueError("input_data_path names no datasets")
    if exchange is None:
        exchange = default_exchange() if partitioned else None
    if not partitioned or jax.process_index() == 0:
        os.makedirs(output_dir, exist_ok=True)
    if exchange is not None:
        exchange.barrier("scoring/output_dir")
    model, index_maps, feature_shards, entity_vocabs, re_columns = (
        _load_scoring_model(
            model_input_dir=model_input_dir,
            index_maps_dir=index_maps_dir,
            feature_shards=feature_shards,
            compact_random_effect_threshold=compact_random_effect_threshold,
        )
    )

    mesh = None
    if distributed or mesh_shape:
        from photon_ml_tpu.parallel.multihost import make_hybrid_mesh

        shape = dict(mesh_shape or {})
        mesh = make_hybrid_mesh(shape.get("data"), shape.get("model", 1))
        if shape.get("model", 1) > 1:
            fe_feature_sharded = True
        logger.info(
            "distributed scoring: mesh %s over %d devices",
            dict(zip(mesh.axis_names, mesh.devices.shape)), mesh.devices.size,
        )
    elif jax.device_count() > 1:
        logger.warning(
            "scoring on 1 of %d devices — %d stay idle; pass --distributed "
            "(or --mesh data=N,model=M) to use them",
            jax.device_count(), jax.device_count() - 1,
        )

    pad_multiple = 1
    if exchange is not None:
        data_axis = int(mesh.shape["data"])
        if data_axis % exchange.num_ranks:
            raise ValueError(
                f"--partitioned-io: mesh data axis {data_axis} must be a "
                f"multiple of the process count {exchange.num_ranks}"
            )
        pad_multiple = data_axis // exchange.num_ranks

    # the model half of the scoring work is built ONCE and reused across
    # the per-dataset loop: the transformer keeps its DistributedScorer,
    # whose placed params are cached per layout (params_for_layouts) — a
    # multi-dataset run pays model parse + device placement exactly once
    transformer = GameTransformer(
        model=model, evaluator_specs=tuple(evaluators),
        mesh=mesh, fe_feature_sharded=fe_feature_sharded,
    )
    part_scorer = None
    summaries: list[dict] = []
    for di, path in enumerate(paths):
        ds_output = (
            output_dir if len(paths) == 1
            else os.path.join(output_dir, f"dataset-{di:04d}")
        )
        if ds_output != output_dir:
            if not partitioned or jax.process_index() == 0:
                os.makedirs(ds_output, exist_ok=True)
            if exchange is not None:
                exchange.barrier(f"scoring/output_dir/{di}")

        with Timed("read scoring data"):
            from photon_ml_tpu.resilience import default_io_policy

            def _read(_path=path):
                return read_partitioned(
                    _path,
                    feature_shards,
                    exchange=exchange,
                    index_maps=index_maps or None,
                    random_effect_id_columns=re_columns,
                    evaluation_id_columns=evaluation_id_columns(evaluators),
                    entity_vocabs=entity_vocabs,
                    fmt=input_format,
                    pad_multiple=pad_multiple,
                    on_corrupt=on_corrupt,
                )

            # transient-I/O retry only on the non-collective path: retrying
            # one rank of an exchange-coordinated read would desynchronize
            # the SPMD exchange sequence (the collective path has deadlines
            # instead)
            part = (
                _read() if exchange is not None
                else default_io_policy().call(
                    _read, description="read scoring data"
                )
            )
            data = part.result
        partition = part.partition

        if partition.num_ranks > 1:
            # partitioned scoring: the [n] score vector stays mesh-sharded
            # end to end; each rank device-gets only its rows and writes
            # its own part file — no process_allgather funnel, no rank-0
            # encode of the full output (ScoreProcessingUtils.scala
            # per-partition layout)
            from photon_ml_tpu.io.score_writer import ShardedScoreWriter
            from photon_ml_tpu.parallel.scoring import DistributedScorer

            with Timed("score"):
                if part_scorer is None:
                    part_scorer = DistributedScorer(
                        model, mesh, fe_feature_sharded=fe_feature_sharded
                    )
                local_scores = part_scorer.score_partitioned(
                    {partition.rank: data.dataset}, partition,
                    exchange=exchange,
                )[partition.rank]
            n_local = partition.local_n
            with Timed("save scores"):
                ShardedScoreWriter(
                    os.path.join(ds_output, "scores"), exchange=exchange
                ).write(
                    local_scores,
                    model_id=model_id,
                    uids=np.asarray(data.dataset.unique_ids)[:n_local],
                    labels=np.asarray(
                        data.dataset.host_array("labels")
                    )[:n_local],
                    weights=np.asarray(
                        data.dataset.host_array("weights")
                    )[:n_local],
                )
            summary = {
                "num_scored": partition.total_true_rows,
                "num_scored_local": n_local,
                "bytes_decoded_local": part.bytes_decoded,
                "input_bytes_total": part.input_bytes_total,
                "evaluations": {},
            }
        else:
            with Timed("score"):
                scored = transformer.transform(data.dataset)

            summary = {
                "num_scored": int(len(scored.scores)),
                "evaluations": scored.evaluations,
            }
            # multi-process rule: every rank participated in the scoring
            # collectives above (DistributedScorer gathers across
            # processes); only rank 0 touches the shared output directory
            if jax.process_index() == 0:
                with Timed("save scores"):
                    write_scores(
                        os.path.join(ds_output, "scores"),
                        scored.scores,
                        records_per_file=1 << 20,
                        model_id=model_id,
                        uids=scored.unique_ids,
                        labels=np.asarray(data.dataset.host_array("labels")),
                        weights=np.asarray(
                            data.dataset.host_array("weights")
                        ),
                    )
        if len(paths) > 1:
            summary = dict(summary, input_data_path=str(path))
        summary = dict(
            summary, runtime=runtime_stamp(),
            decode_paths={"score": data.decode_path},
        )
        if jax.process_index() == 0:
            with open(
                os.path.join(ds_output, "scoring-summary.json"), "w"
            ) as f:
                from photon_ml_tpu.cli.game_training_driver import _json_safe

                json.dump(_json_safe(summary), f, indent=2, default=float)
        summaries.append(summary)
        if journal is not None:
            # per-dataset liveness heartbeat (ISSUE 12): which dataset the
            # multi-dataset loop last finished, with registry deltas, in
            # the crash-durable journal stage; inert on worker ranks
            from photon_ml_tpu.telemetry import default_registry

            journal.heartbeat(
                registry=default_registry(), stage="game_scoring",
                dataset_index=di, num_datasets=len(paths),
                num_scored=summary.get("num_scored"),
            )

    if len(paths) == 1:
        return summaries[0]
    combined = {
        "num_scored": int(sum(s["num_scored"] for s in summaries)),
        "num_datasets": len(summaries),
        "datasets": summaries,
        "runtime": runtime_stamp(),
    }
    if jax.process_index() == 0:
        with open(os.path.join(output_dir, "scoring-summary.json"), "w") as f:
            from photon_ml_tpu.cli.game_training_driver import _json_safe

            json.dump(_json_safe(combined), f, indent=2, default=float)
    return combined


def build_arg_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(prog="game_scoring_driver")
    p.add_argument("--input-data-path", required=True, action="append",
                   help="dataset to score; repeat to score several datasets "
                        "in one run (the model is parsed and placed ONCE; "
                        "each dataset writes under "
                        "<output-dir>/dataset-NNNN)")
    p.add_argument("--model-input-dir", required=True)
    p.add_argument("--output-dir", required=True)
    p.add_argument("--feature-shard-configurations", action="append", default=None)
    p.add_argument("--index-maps-dir")
    p.add_argument("--evaluators", default="")
    p.add_argument("--model-id", default="")
    p.add_argument("--input-format", default="avro", choices=["avro", "libsvm"])
    p.add_argument("--compact-random-effect-threshold", type=int,
                   default=DEFAULT_COMPACT_RE_THRESHOLD,
                   help="random-effect coordinates whose feature space "
                        "exceeds this load as compact per-entity tables "
                        "(never materializing [entities, dim])")
    p.add_argument("--distributed", action="store_true",
                   help="score through the mesh-sharded SPMD scoring "
                        "program over all devices")
    p.add_argument("--mesh", default="",
                   help="device mesh layout 'data=8,model=1' (implies "
                        "--distributed; model>1 shards the fixed-effect "
                        "feature/coefficient axis — required for "
                        "column-sharded giant-d models)")
    p.add_argument("--partitioned-io", action="store_true",
                   help="multi-process runs: each rank decodes ~1/P of the "
                        "input and writes its own part-NNNNN.avro score "
                        "shard into the SHARED --output-dir (no "
                        "process_allgather funnel; no --evaluators yet)")
    p.add_argument("--on-corrupt", default="raise",
                   choices=["raise", "quarantine"],
                   help="corrupt Avro blocks: 'raise' (strict, default) "
                        "or 'quarantine' (skip-and-count; spans journaled)")
    p.add_argument("--telemetry-dir",
                   help="write a rank-0 JSONL run journal (phase timings, "
                        "io + resilience counters) here — on the failure "
                        "path too")
    p.add_argument("--trace-dir",
                   help="write per-rank Chrome-trace span timelines "
                        "(trace-{rank:05d}.json, open in Perfetto) + a "
                        "rank-merged straggler report here; flushed on "
                        "success and failure, spans still open included "
                        "(the device-free timeline: a jax.profiler "
                        "session holds the same spans beside the device)")
    return p


def main(argv: Sequence[str] | None = None) -> dict:
    logging.basicConfig(level=logging.INFO)
    from photon_ml_tpu.util.compile_cache import configure_compile_cache

    configure_compile_cache()
    args = build_arg_parser().parse_args(argv)
    shards = None
    if args.feature_shard_configurations:
        shards = dict(
            parse_feature_shard_config(s) for s in args.feature_shard_configurations
        )
    paths = args.input_data_path
    return run(
        input_data_path=paths[0] if len(paths) == 1 else paths,
        model_input_dir=args.model_input_dir,
        output_dir=args.output_dir,
        feature_shards=shards,
        index_maps_dir=args.index_maps_dir,
        evaluators=tuple(x.strip() for x in args.evaluators.split(",") if x.strip()),
        model_id=args.model_id,
        input_format=args.input_format,
        compact_random_effect_threshold=args.compact_random_effect_threshold,
        distributed=args.distributed,
        mesh_shape=_parse_mesh_shape(args.mesh),
        partitioned_io=args.partitioned_io,
        on_corrupt=args.on_corrupt,
        telemetry_dir=args.telemetry_dir,
        trace_dir=args.trace_dir,
    )


if __name__ == "__main__":
    main()
