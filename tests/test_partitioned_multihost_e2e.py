"""Two-REAL-process e2e for the partitioned host-I/O layer.

Two OS processes rendezvous through jax.distributed; each rank then:

- decodes ONLY its slice of the Avro input through
  io/partitioned_reader.read_partitioned (metadata consistency over the
  coordination-service KV exchange — parallel/multihost.DistributedKVExchange),
- proves it via the per-rank ``io/partitioned/*`` telemetry counters
  (each rank's bytes decoded are strictly less than the full input; the
  two slices cover it exactly),
- writes its OWN ``part-NNNNN.avro`` score shard into the SHARED output
  directory (io/score_writer.ShardedScoreWriter; rank-0-only directory
  creation + KV barrier),
- dumps its decoded block for the parent's model-identity check.

The parent then asserts (a) a model trained from the two worker-decoded
blocks through ``train_partitioned`` is identical to the full-read
``train_distributed`` model, (b) the per-rank score shards, concatenated
in part order, equal the rank-0 writer's output record for record, and
(c) the per-rank bytes-decoded telemetry shows each rank read strictly
less than the full input.

The workers do HOST work only (decode, exchange, write): this container's
CPU jaxlib cannot run cross-process device computations (the known
limitation behind the 4 pre-existing test_multihost_e2e failures), so the
device side of the partitioned path — assembly, training, scoring parity —
is exercised in-process on the virtual mesh (here and in
tests/test_partitioned_io.py) over the REAL worker-decoded blocks.
"""

import json
import os
import socket
import subprocess
import sys
import textwrap
import threading

import numpy as np
import pytest

TESTS_DIR = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(TESTS_DIR)
sys.path.insert(0, TESTS_DIR)

from test_partitioned_io import (  # noqa: E402
    SHARD_CONFIGS,
    _write_input,
)


def _skip_or_fail(reason: str):
    if os.environ.get("PHOTON_REQUIRE_MULTIHOST"):
        pytest.fail(f"PHOTON_REQUIRE_MULTIHOST is set but: {reason}")
    pytest.skip(reason)


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


WORKER = textwrap.dedent(
    """
    import os, sys, json
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax
    jax.config.update("jax_enable_x64", True)
    sys.path.insert(0, {repo!r})
    from photon_ml_tpu.parallel import multihost

    pid, port, data_dir = int(sys.argv[1]), sys.argv[2], sys.argv[3]
    multihost.initialize(
        coordinator_address=f"127.0.0.1:{{port}}", num_processes=2, process_id=pid
    )
    assert jax.process_count() == 2
    import numpy as np
    from photon_ml_tpu.io.data_reader import FeatureShardConfiguration
    from photon_ml_tpu.io.partitioned_reader import read_partitioned
    from photon_ml_tpu.io.score_writer import ShardedScoreWriter
    from photon_ml_tpu.telemetry import io_counters

    cfgs = {{
        "global": FeatureShardConfiguration(feature_bags=("features",)),
        "perUser": FeatureShardConfiguration(
            feature_bags=("entityFeatures",), has_intercept=False
        ),
    }}
    exchange = multihost.default_exchange()
    assert exchange.num_ranks == 2 and exchange.rank == pid
    part = read_partitioned(
        data_dir + "/input", cfgs, exchange=exchange,
        random_effect_id_columns=("userId",), pad_multiple=2,
    )
    ds = part.result.dataset
    n = part.partition.local_n

    # per-rank score shard from the local block (host-computed with a
    # coefficient vector both sides derive from the feature keys; the
    # device-side scoring parity is covered in-process — this container
    # cannot run cross-process device computations)
    def hash_w(k):
        return (sum(ord(c) for c in (k or "")) % 13) / 7.0

    x = np.asarray(ds.host_array("shard/global"))[:n]
    gmap = part.result.index_maps["global"]
    w = np.asarray([hash_w(gmap.get_feature_name(j)) for j in range(gmap.size)])
    scores = x @ w + np.asarray(ds.host_array("offsets"))[:n]
    ShardedScoreWriter(data_dir + "/scores", exchange=exchange).write(
        scores, model_id="e2e",
        uids=np.asarray(ds.unique_ids)[:n],
        labels=np.asarray(ds.host_array("labels"))[:n],
        weights=np.asarray(ds.host_array("weights"))[:n],
    )

    # decoded block for the parent's model-identity check
    np.savez(
        data_dir + f"/rank{{pid}}.npz",
        labels=np.asarray(ds.host_array("labels")),
        offsets=np.asarray(ds.host_array("offsets")),
        weights=np.asarray(ds.host_array("weights")),
        g=np.asarray(ds.host_array("shard/global")),
        ru=np.asarray(ds.host_array("shard/perUser")),
        entity_idx=np.asarray(ds.host_array("entity_idx/userId")),
        uids=np.asarray(ds.unique_ids),
        vocab=np.asarray(ds.entity_vocabs["userId"]).astype(str),
        local_rows=np.asarray(part.partition.local_rows),
        presence=part.entity_rank_presence["userId"],
    )
    print("PART " + json.dumps({{
        "rank": pid,
        "mode": part.mode,
        "local_n": n,
        "block_rows": part.partition.block_rows,
        "bytes": part.bytes_decoded,
        "total": part.input_bytes_total,
        "counter_bytes": io_counters.bytes_decoded(),
        "counter_total": io_counters.input_bytes_total(),
        "files": [os.path.basename(f) for f in part.local_files],
    }}), flush=True)
    """
)


def test_two_process_partitioned_ingest_and_sharded_score_output(tmp_path):
    os.makedirs(tmp_path / "input", exist_ok=True)
    _write_input(tmp_path / "input", num_files=4, rows_per_file=40, seed=5)

    script = tmp_path / "worker.py"
    script.write_text(WORKER.format(repo=REPO))
    port = _free_port()
    env = dict(os.environ)
    procs = [
        subprocess.Popen(
            [sys.executable, str(script), str(i), str(port), str(tmp_path)],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            env=env,
        )
        for i in range(2)
    ]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=300)
            outs.append((p.returncode, out))
    except subprocess.TimeoutExpired:
        for p in procs:
            p.kill()
        _skip_or_fail("distributed coordinator rendezvous timed out")

    reports = []
    for rc, out in outs:
        if rc != 0 and "initialize" in out:
            _skip_or_fail(f"jax.distributed unavailable: {out[-300:]}")
        assert rc == 0, out
        line = [l for l in out.splitlines() if l.startswith("PART ")]
        assert line, out
        reports.append(json.loads(line[0][len("PART "):]))
    reports.sort(key=lambda r: r["rank"])

    # ---- (c) per-rank bytes-decoded telemetry: each rank read STRICTLY
    # less than the full input; together they cover it (file mode)
    total = reports[0]["total"]
    assert total > 0
    for r in reports:
        assert 0 < r["bytes"] < total
        assert r["counter_bytes"] == r["bytes"]  # the registry counter
        assert r["counter_total"] == total
        assert r["mode"] == "files"
    assert reports[0]["bytes"] + reports[1]["bytes"] == total
    # disjoint contiguous file assignment
    assert not (set(reports[0]["files"]) & set(reports[1]["files"]))

    # ---- full-read reference (parent, single-process)
    from photon_ml_tpu.io.data_reader import read_merged
    from photon_ml_tpu.io import avro as avro_io
    from photon_ml_tpu.io.model_io import write_scores

    full = read_merged(str(tmp_path / "input"), SHARD_CONFIGS,
                       random_effect_id_columns=("userId",))
    gmap = full.index_maps["global"]
    w = np.asarray([
        (sum(ord(c) for c in (gmap.get_feature_name(j) or "")) % 13) / 7.0
        for j in range(gmap.size)
    ])
    ref_scores = (
        np.asarray(full.dataset.host_array("shard/global")) @ w
        + np.asarray(full.dataset.host_array("offsets"))
    )
    write_scores(
        str(tmp_path / "scores-ref"), ref_scores, model_id="e2e",
        uids=np.asarray(full.dataset.unique_ids),
        labels=np.asarray(full.dataset.host_array("labels")),
        weights=np.asarray(full.dataset.host_array("weights")),
        records_per_file=1 << 20,
    )

    # ---- (b) per-rank score shards, concatenated in part order, equal the
    # rank-0 writer's output record for record
    parts = sorted(os.listdir(tmp_path / "scores"))
    assert parts == ["part-00000.avro", "part-00001.avro"]
    got = [r for p in parts
           for r in avro_io.read_container(tmp_path / "scores" / p)]
    want = [r for p in sorted(os.listdir(tmp_path / "scores-ref"))
            for r in avro_io.read_container(tmp_path / "scores-ref" / p)]
    assert got == want

    # ---- (a) the worker-decoded blocks train to the SAME model as the
    # full read (device work runs in-process on the virtual mesh — this
    # jaxlib cannot run cross-process computations)
    from photon_ml_tpu.data.game_data import (
        GameDataset,
        build_random_effect_dataset,
        build_random_effect_dataset_partitioned,
    )
    from photon_ml_tpu.io.partitioned_reader import PartitionInfo
    from photon_ml_tpu.parallel.multihost import (
        InProcessExchange,
        make_hybrid_mesh,
    )
    from photon_ml_tpu.parallel.distributed import (
        train_distributed,
        train_partitioned,
    )
    from test_partitioned_io import _toy_programs

    blocks = [np.load(tmp_path / f"rank{r}.npz", allow_pickle=False)
              for r in range(2)]
    local_rows = tuple(int(x) for x in blocks[0]["local_rows"])
    assert local_rows == tuple(r["local_n"] for r in reports)
    partitions = [
        PartitionInfo(r, 2, local_rows, reports[0]["block_rows"])
        for r in range(2)
    ]

    def dataset_of(z):
        return GameDataset(
            unique_ids=z["uids"],
            labels=z["labels"],
            offsets=z["offsets"],
            weights=z["weights"],
            feature_shards={"global": z["g"], "perUser": z["ru"]},
            entity_idx={"userId": z["entity_idx"]},
            entity_vocabs={"userId": z["vocab"]},
        )

    datasets = [dataset_of(z) for z in blocks]
    np.testing.assert_array_equal(blocks[0]["vocab"], blocks[1]["vocab"])
    assert int(np.max(blocks[0]["presence"])) == 1  # entity-clustered

    exchanges = InProcessExchange.create_group(2)
    re_parts = [None, None]

    def build(r):
        re_parts[r] = {"userId": build_random_effect_dataset_partitioned(
            datasets[r], "userId", "perUser",
            partition=partitions[r], exchange=exchanges[r],
            bucket_sizes=(64,), lane_multiple=2,
        )}

    threads = [threading.Thread(target=build, args=(r,)) for r in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()

    make_program = _toy_programs()
    mesh = make_hybrid_mesh(data=4, model=2)
    res = train_partitioned(
        make_program(),
        {r: (datasets[r], re_parts[r]) for r in range(2)},
        mesh, 2, num_iterations=2,
    )
    full_re = {"userId": build_random_effect_dataset(
        full.dataset, "userId", "perUser", bucket_sizes=(64,),
    )}
    ref = train_distributed(make_program(), full.dataset, full_re,
                            mesh=mesh, num_iterations=2)
    np.testing.assert_allclose(res.losses, ref.losses, rtol=1e-12)
    np.testing.assert_allclose(
        np.asarray(res.state.fe_coefficients),
        np.asarray(ref.state.fe_coefficients), rtol=1e-9, atol=1e-12,
    )
    np.testing.assert_allclose(
        np.asarray(res.state.re_tables["userId"]),
        np.asarray(ref.state.re_tables["userId"]), rtol=1e-9, atol=1e-12,
    )
