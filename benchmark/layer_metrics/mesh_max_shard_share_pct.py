"""The largest share of a sharded input array that any one device holds, in
percent: 100 x the larger of the program's gauges
``mesh/sample_arrays/max_shard_fraction`` and
``mesh/entity_arrays/max_shard_fraction`` (``train_distributed`` sets them
from the placed arrays' metadata). 25 when four chips share every array, 100
when one chip holds one whole. A program without the gauges reads nothing."""


def read(ctx):
    from photon_ml_tpu.telemetry.registry import default_registry

    gauges = default_registry().snapshot()["gauges"]
    shares = [gauges.get(f"mesh/{group}/max_shard_fraction")
              for group in ("sample_arrays", "entity_arrays")]
    shares = [s for s in shares if s is not None]
    return 100.0 * max(shares) if shares else None
