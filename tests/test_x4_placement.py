"""A GLMix fit from HOST arrays on a ``data=4`` mesh: placed shard by shard,
packed for the mesh, and the same fit as on one device.

Attempt 1 of the four-chip benchmark cell died with the whole 20 GB X
assembled on chip 0 (PERF.md 6). The rule these tests hold the program to:
no device ever holds more than its own shard of an array whose leading axis
is rows, lanes or entities.

Since PR 39 also: a chip gathers its own lanes' offsets and nobody else's.
The ``[n]`` vectors a coordinate's buckets index are brought whole to every
chip once a coordinate (``GameTrainProgram._whole_on_every_chip``), read here
in the compiled step's text.
"""

import importlib.util
import os
import re

import jax
import numpy as np
import pytest

from photon_ml_tpu.algorithm.mf_coordinate import build_mf_dataset
from photon_ml_tpu.data.game_data import GameDataset, build_random_effect_dataset
from photon_ml_tpu.optim.optimizer import OptimizerConfig, OptimizerType
from photon_ml_tpu.parallel.distributed import (
    EXCHANGES_TRACED,
    FixedEffectStepSpec,
    GameTrainProgram,
    MatrixFactorizationStepSpec,
    RandomEffectStepSpec,
    train_distributed,
)
from photon_ml_tpu.parallel.mesh import make_mesh
from photon_ml_tpu.telemetry.program_ledger import scopes_of_text
from photon_ml_tpu.telemetry.registry import default_registry
from photon_ml_tpu.telemetry.tracing import Tracer, install_tracer, uninstall_tracer
from photon_ml_tpu.types import TaskType

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROWS, D_GLOBAL, D_ENTITY = 8192, 32, 8
USERS, ITEMS = 96, 50
LADDER = (8, 32, 128, 512)
RE = (("user", "per_user"), ("item", "per_item"))
COLLECTIVES = ("all-reduce", "all-gather", "all-to-all", "reduce-scatter",
               "collective-permute")
COLLECTIVE = re.compile(r"= (.*?) (" + "|".join(COLLECTIVES) + r")(-start|-done)?\(")
SHAPE = re.compile(r"\w+\[([\d,]*)\]")
TYPED_SHAPE = re.compile(r"(\w+)\[([\d,]*)\]")
MF_RANK, MF_ALTERNATIONS = 4, 2


def _host_data() -> dict:
    rng = np.random.default_rng(27)
    x = {"global": rng.standard_normal((ROWS, D_GLOBAL)).astype(np.float32),
         "per_user": rng.standard_normal((ROWS, D_ENTITY)).astype(np.float32),
         "per_item": rng.standard_normal((ROWS, D_ENTITY)).astype(np.float32)}
    for block in x.values():
        block[:, -1] = 1.0
    # skewed entity sizes: several rungs of the ladder, lane counts that do
    # not divide by four unless the packer makes them
    user = np.minimum((rng.pareto(1.2, ROWS) * 6).astype(np.int32), USERS - 1)
    item = np.minimum((rng.pareto(0.9, ROWS) * 3).astype(np.int32), ITEMS - 1)
    w = rng.standard_normal(D_GLOBAL).astype(np.float32) * 0.3
    wu = rng.standard_normal((USERS, D_ENTITY)).astype(np.float32) * 0.4
    wi = rng.standard_normal((ITEMS, D_ENTITY)).astype(np.float32) * 0.4
    margin = (x["global"] @ w + np.sum(x["per_user"] * wu[user], 1)
              + np.sum(x["per_item"] * wi[item], 1))
    y = (rng.random(ROWS) < 1 / (1 + np.exp(-margin))).astype(np.float32)
    return {"x": x, "y": y, "user": user, "item": item}


def _dataset(host: dict) -> GameDataset:
    """Every field a HOST array: nothing is on a device yet."""
    n = len(host["y"])
    return GameDataset(
        unique_ids=np.arange(n, dtype=np.int64), labels=host["y"],
        offsets=np.zeros(n, np.float32), weights=np.ones(n, np.float32),
        feature_shards=dict(host["x"]),
        entity_idx={"user": host["user"], "item": host["item"]},
        entity_vocabs={"user": np.arange(USERS).astype(str),
                       "item": np.arange(ITEMS).astype(str)})


def _program(mesh) -> GameTrainProgram:
    optimizer = OptimizerConfig(optimizer_type=OptimizerType.LBFGS, max_iterations=10,
                                rel_function_tolerance=1e-6)
    return GameTrainProgram(
        TaskType.LOGISTIC_REGRESSION,
        FixedEffectStepSpec("global", optimizer, l2_weight=1.0),
        tuple(RandomEffectStepSpec(t, s, optimizer, l2_weight=1.0) for t, s in RE),
        use_pallas_fe=None, mesh=mesh)


def _fit(host: dict, devices: int):
    mesh = make_mesh(devices, 1, devices=jax.devices()[:devices])
    dataset = _dataset(host)
    packed = {t: build_random_effect_dataset(dataset, t, s, bucket_sizes=LADDER,
                                             mesh=mesh) for t, s in RE}
    result = train_distributed(_program(mesh), dataset, packed, mesh=mesh,
                               num_iterations=3)
    state = {"fe": np.asarray(result.state.fe_coefficients),
             **{t: np.asarray(result.state.re_tables[t]) for t, _ in RE}}
    return state, result.losses, packed


def _bytes_by_device(arrays) -> dict:
    """From the shardings alone (``shard.data`` would make new live arrays)."""
    held = {d.id: 0 for d in jax.devices()}
    for a in arrays:
        share = int(np.prod(a.sharding.shard_shape(a.shape))) * a.dtype.itemsize
        for device in a.sharding.addressable_devices:
            held[device.id] += share
    return held


def _live_bytes() -> dict:
    return _bytes_by_device(jax.live_arrays())


@pytest.fixture(scope="module")
def host():
    return _host_data()


@pytest.fixture(scope="module")
def four(host):
    tracer = install_tracer(Tracer(rank=0))
    try:
        state, losses, packed = _fit(host, 4)
    finally:
        uninstall_tracer()
    gauges = default_registry().snapshot()["gauges"]
    return {"state": state, "losses": losses, "packed": packed, "gauges": gauges,
            "events": tracer.events()}


def _rel(a, b):
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def test_four_devices_from_host_arrays_agree_with_one_device(host, four):
    """Tolerances: float32 sums taken in another order end line searches an
    evaluation earlier or later (PR 21 read 5e-3 between one and four chips
    at full size); here, measured once: fe 3.6e-5, user 3.4e-4, item 1.9e-4,
    losses 4.3e-6. The limits are three times that."""
    one, losses, _ = _fit(host, 1)
    assert _rel(four["state"]["fe"], one["fe"]) < 1.1e-4
    assert _rel(four["state"]["user"], one["user"]) < 1e-3
    assert _rel(four["state"]["item"], one["item"]) < 6e-4
    np.testing.assert_allclose(four["losses"], losses, rtol=1.3e-5)
    assert four["losses"][0] > four["losses"][-1]


def test_four_devices_agree_with_the_plain_reference(host, four):
    """The benchmark's reference for the four-chip configuration (block
    coordinate descent, every block by Newton, rows in blocks over the
    devices it is given, nothing imported from the program), at this size.
    Measured once: fe 4.2e-5, user 5.4e-4, item 4.4e-4, losses 8.7e-6 (the
    one-device fit reads 2.7e-5, 5.1e-4, 4.6e-4: ten L-BFGS iterations stop
    that far from Newton's minimizer on either mesh); the loss at the
    program's own coefficients against float64 7.8e-9. Three times that."""
    path = os.path.join(ROOT, "benchmark", "references", "glmix-ml20m-x4.py")
    with open(path) as f:
        assert "photon_ml_tpu" not in f.read().split('"""', 2)[2]
    spec = importlib.util.spec_from_file_location("reference_x4", path)
    reference = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reference)
    n = len(host["y"])
    kept = {}
    for t, _ in RE:  # the ladder's top rung caps an entity
        mask = np.zeros(n, bool)
        for bucket in four["packed"][t].buckets:
            rows = np.asarray(bucket.sample_rows).ravel()
            mask[rows[rows >= 0]] = True
        kept[t] = mask
    split = {"x_global": host["x"]["global"], "x_user": host["x"]["per_user"],
             "x_item": host["x"]["per_item"], "y": host["y"],
             "user": host["user"], "item": host["item"]}
    data = {"train": split, "validation": {k: v[:512] for k, v in split.items()}}
    cfg = {"l2_weight": 1.0, "coordinate_descent_iterations": 3,
           "users": {"count": USERS}, "items": {"count": ITEMS}}
    expected = reference.fit(data, cfg, kept, jax.devices()[:4])
    assert _rel(four["state"]["fe"], expected["fe"]) < 1.3e-4
    assert _rel(four["state"]["user"], expected["user"]) < 1.6e-3
    assert _rel(four["state"]["item"], expected["item"]) < 1.3e-3
    np.testing.assert_allclose(four["losses"], expected["losses"], rtol=2.6e-5)
    evaluated = reference.evaluate(data, four["state"])
    assert abs(evaluated["loss"] - four["losses"][-1]) < 2.4e-8 * evaluated["loss"]


def test_every_placed_array_is_shared_by_the_four_devices(four):
    gauges = four["gauges"]
    assert gauges["mesh/sample_arrays/max_shard_fraction"] == 0.25
    assert gauges["mesh/entity_arrays/max_shard_fraction"] == 0.25
    assert gauges["mesh/sample_arrays/devices"] == 4
    assert gauges["mesh/entity_arrays/devices"] == 4
    # the chips were handed the same bytes, to within the replicated state
    most, least = (gauges["mesh/placed_bytes/max_device"],
                   gauges["mesh/placed_bytes/min_device"])
    assert 0 < least <= most < 1.001 * least
    # lanes packed for the mesh: shard_inputs found nothing to pad
    for t, _ in RE:
        for bucket in four["packed"][t].buckets:
            assert bucket.num_entities % 4 == 0
            assert len(bucket.features.sharding.device_set) == 4
            rows = np.asarray(bucket.entity_rows)
            pad = rows == np.iinfo(np.int32).max
            assert not np.asarray(bucket.weights)[pad].any()
            assert (np.asarray(bucket.sample_rows)[pad] == -1).all()
    # every row of an entity in ONE lane: the exact fit
    for t, _ in RE:
        rows = np.concatenate([np.asarray(b.entity_rows) for b in four["packed"][t].buckets])
        rows = rows[rows != np.iinfo(np.int32).max]
        assert len(np.unique(rows)) == len(rows)


def test_host_arrays_are_assembled_under_their_own_span(four):
    assembled = [e for e in four["events"] if e.name == "train/shard/assemble"]
    groups = {e.attrs["group"] for e in assembled}
    assert {"data", "buckets"} <= groups
    by_group = {g: sum(e.attrs["bytes"] for e in assembled if e.attrs["group"] == g)
                for g in groups}
    # X, the two entity blocks, labels, offsets, weights, two index vectors
    assert by_group["data"] == ROWS * 4 * (D_GLOBAL + 2 * D_ENTITY + 5)


def test_no_device_ever_holds_more_than_its_own_shard(host):
    """What would have caught attempt 1: live bytes per device, sampled at
    every placement, never pass the device's own share of what ends up placed
    by more than the coefficient state (which is built on the default device
    and laid out from there: kilobytes against the rows' megabytes)."""
    mesh = make_mesh(4, 1, devices=jax.devices()[:4])
    before = _live_bytes()
    samples = []

    def sample():
        held = _live_bytes()
        samples.append({d: held[d] - before[d] for d in held})

    def recording_put(x, sharding):
        out = jax.device_put(x, sharding)
        sample()
        return out

    dataset = _dataset(host)
    program = _program(mesh)
    packed = {t: build_random_effect_dataset(dataset, t, s, bucket_sizes=LADDER,
                                             mesh=mesh) for t, s in RE}
    sample()
    data, buckets = program.prepare_inputs(dataset, packed)
    state = program.init_state(dataset, packed)
    state_bytes = sum(x.nbytes for x in jax.tree_util.tree_leaves(state))
    sample()
    placed = program.shard_inputs(mesh, data, buckets, state, put_fn=recording_put)
    sample()
    own = _bytes_by_device(jax.tree_util.tree_leaves(placed))
    own = {d.id: own[d.id] for d in mesh.devices.flat}
    rows_bytes = ROWS * 4 * (D_GLOBAL + 2 * D_ENTITY + 5)
    assert min(own.values()) > rows_bytes / 4  # the rows dominate
    for held in samples:
        for device, share in own.items():
            assert held[device] <= share + 3 * state_bytes, (device, held, own)
    for device in jax.devices()[4:]:  # and nothing lands off the mesh
        assert samples[-1][device.id] == 0


def test_the_step_under_data_4_moves_no_rows_by_width_array(host, four):
    """The partitioner's collectives in the compiled sweep: predicates,
    [E, d] tables, [n] vectors, each bucket's row indices and offsets. None
    carries a rows x width array: every operand is smaller than the smallest
    per-entity feature block a chip holds (rows x 8)."""
    mesh = make_mesh(4, 1, devices=jax.devices()[:4])
    program = _program(mesh)
    dataset = _dataset(host)
    data, buckets = program.prepare_inputs(dataset, four["packed"])
    data, buckets, state = program.shard_inputs(
        mesh, data, buckets, program.init_state(dataset, four["packed"]))
    text = program._step.lower(data, buckets, state).compile().as_text()
    found = []
    for line in text.splitlines():
        m = COLLECTIVE.search(line)
        if m:
            sizes = [int(np.prod([int(s) for s in dims.split(",") if s] or [1]))
                     for dims in SHAPE.findall(m.group(1))]
            found.append((m.group(2), max(sizes), m.group(1)))
    assert found, "a four-device sweep without a collective is not sharded"
    assert {"all-reduce", "all-gather"} <= {op for op, _, _ in found}
    widest = max(found, key=lambda f: f[1])
    assert widest[1] < ROWS * D_ENTITY / 4, widest


# -- PR 39: the [n] vectors are exchanged once a coordinate -------------------


def _mf_program(mesh) -> GameTrainProgram:
    optimizer = OptimizerConfig(optimizer_type=OptimizerType.LBFGS, max_iterations=10,
                                rel_function_tolerance=1e-6)
    return GameTrainProgram(
        TaskType.LOGISTIC_REGRESSION,
        FixedEffectStepSpec("global", optimizer, l2_weight=1.0),
        mf_specs=(MatrixFactorizationStepSpec(
            "mf", "user", "item", MF_RANK, optimizer, l2_weight=1.0,
            num_alternations=MF_ALTERNATIONS),),
        use_pallas_fe=None, mesh=mesh)


def _without_exchange(program: GameTrainProgram) -> GameTrainProgram:
    """The program as its parent lowered it on a mesh."""
    program._exchange = None
    return program


def _mesh(devices):
    return devices and make_mesh(devices, 1, devices=jax.devices()[:devices])


def _packed(host, mesh) -> dict:
    dataset = _dataset(host)
    return {t: build_random_effect_dataset(dataset, t, s, bucket_sizes=LADDER, mesh=mesh)
            for t, s in RE}


def _mf_packed(host) -> dict:
    return {"mf": build_mf_dataset(_dataset(host), "user", "item", bucket_sizes=LADDER)}


def _placed(program, mesh, host, packed=None, mf=None):
    """(data, buckets, state) of the program, laid over the mesh if one is given."""
    inputs = (_dataset(host), packed or {}, mf)
    data, buckets = program.prepare_inputs(*inputs)
    state = program.init_state(*inputs)
    if mesh is None:
        return data, buckets, state
    return program.shard_inputs(mesh, data, buckets, state)


def _traced_exchanges(program, placed) -> int:
    """What tracing the step once adds to the registry's counter."""
    counter = default_registry().counter(EXCHANGES_TRACED)
    before = counter.value
    data, buckets, state = placed
    jax.eval_shape(program._step_impl, data, buckets, program._carried(data, state))
    return counter.value - before


def _compiled(program, placed) -> dict:
    """The optimized step's collectives as (operation, [(dtype, dims)], op_name)
    and its scalar gathers (one element a slot) as (slots written, op_name),
    from the program's own record of its compiled text."""
    text = program._step.lower(*placed).compile().as_text()
    collectives, scalar_gathers = [], []
    for signature, name in scopes_of_text(text).instructions.values():
        opcode = re.search(r"[\w-]+$", signature).group()
        shapes = [(dtype, tuple(int(s) for s in dims.split(",") if s))
                  for dtype, dims in TYPED_SHAPE.findall(signature)]
        if opcode.removesuffix("-start") in COLLECTIVES:  # a "-done" repeats its start
            collectives.append((opcode.removesuffix("-start"), shapes, name))
        elif opcode == "gather" and shapes[0][1][1:] == (1,):
            scalar_gathers.append((shapes[0][1][0], name))
    return {"collectives": collectives, "scalar_gathers": scalar_gathers}


def _lanes(buckets_of_a_coordinate) -> list:
    return [tuple(b["sample_rows"].shape) for b in buckets_of_a_coordinate]


@pytest.fixture(scope="module")
def mesh4():
    return make_mesh(4, 1, devices=jax.devices()[:4])


@pytest.fixture(scope="module")
def glmix4(host, four, mesh4):
    program = _program(mesh4)
    placed = _placed(program, mesh4, host, four["packed"])
    return {"lanes": {t: _lanes(placed[1][t]) for t, _ in RE},
            **_compiled(program, placed)}


@pytest.fixture(scope="module")
def mf4(host, mesh4):
    program = _mf_program(mesh4)
    placed = _placed(program, mesh4, host, mf=_mf_packed(host))
    return {"lanes": {side: _lanes(placed[1]["__mf__"]["mf"][side])
                      for side in ("row", "col")},
            **_compiled(program, placed)}


def _index_shapes(lanes) -> set:
    """How a bucket's [e, cap] row indices can appear as a collective's operand."""
    return {shape for e, cap in lanes for shape in ((e, cap), (e, cap, 1))}


def test_without_the_exchange_the_partitioner_gathers_every_lanes_indices(host, four, mesh4):
    """What the other tests of this section read is there to be read: the same
    step with the exchange taken out all-gathers the buckets' ``s32[e, cap, 1]``
    row indices and all-reduces ``f32[e, cap]`` offsets under ``gather``."""
    program = _without_exchange(_program(mesh4))
    placed = _placed(program, mesh4, host, four["packed"])
    assert _traced_exchanges(program, placed) == 0
    compiled = _compiled(program, placed)
    lanes = [shape for t, _ in RE for shape in _lanes(placed[1][t])]
    gathered = {dims for op, shapes, _ in compiled["collectives"] if op == "all-gather"
                for dtype, dims in shapes if dtype == "s32"}
    assert gathered & _index_shapes(lanes)
    reduced = {dims for op, shapes, name in compiled["collectives"]
               if op == "all-reduce" and "/gather/" in name for _, dims in shapes}
    assert reduced & {(e, cap) for e, cap in lanes if cap != D_ENTITY}


@pytest.mark.parametrize("compiled", ["glmix4", "mf4"])
def test_no_collective_carries_a_buckets_row_indices(compiled, request):
    compiled = request.getfixturevalue(compiled)
    lanes = [shape for shapes in compiled["lanes"].values() for shape in shapes]
    forbidden = _index_shapes(lanes)
    for op, shapes, name in compiled["collectives"]:
        for dtype, dims in shapes:
            assert not (dtype == "s32" and dims in forbidden), (op, shapes, name)


def test_under_gather_only_the_warm_starts_are_all_reduced(glmix4):
    """``table[entity_rows]`` stays as it was: the table lies by entities, so a
    bucket's ``[e, d]`` warm starts are summed over the chips. No ``[e, cap]``
    block of offsets is."""
    for t, _ in RE:
        lanes = glmix4["lanes"][t]
        reduced = [dims for op, shapes, name in glmix4["collectives"]
                   if op == "all-reduce" and f"re/{t}/gather/" in name
                   for _, dims in shapes]
        assert len(reduced) <= len(lanes)
        assert set(reduced) <= {(e, D_ENTITY) for e, _ in lanes}, reduced


def test_one_all_gather_of_the_rows_a_random_effect_coordinate(glmix4):
    whole = [(shapes, name) for op, shapes, name in glmix4["collectives"]
             if op == "all-gather" and any(dims == (ROWS,) for _, dims in shapes)]
    assert sorted(name for _, name in whole) == [
        f"jit(_step_impl)/re/{t}/gather/sharding_constraint" for t in ("item", "user")]
    assert all(shapes == [("f32", (ROWS,))] for shapes, _ in whole)


def test_a_chip_gathers_its_own_lanes_offsets_and_nobody_elses(glmix4):
    for t, _ in RE:
        written = sorted(slots for slots, name in glmix4["scalar_gathers"]
                         if f"re/{t}/gather/" in name)
        assert written == sorted(e // 4 * cap for e, cap in glmix4["lanes"][t])


def test_the_factorization_exchanges_each_rows_vector_once_a_half_step(mf4):
    """The offsets, the fixed side's ``[n]`` entity index and its ``[E, k]``
    factors: one all-gather each a half-step, under that half-step's scope."""
    whole = [(shapes, name) for op, shapes, name in mf4["collectives"]
             if op == "all-gather" and any(dims == (ROWS,) for _, dims in shapes)]
    half_steps = sorted(["col", "row"] * MF_ALTERNATIONS)
    for dtype in ("f32", "s32"):
        names = sorted(name for shapes, name in whole if shapes == [(dtype, (ROWS,))])
        assert names == [f"jit(_step_impl)/mf/mf/{side}/gather/sharding_constraint"
                         for side in half_steps], (dtype, whole)
    assert len(whole) == 2 * len(half_steps)
    factors = sorted(name.split("/")[3] for op, shapes, name in mf4["collectives"]
                     if op == "all-gather" and name.endswith("/gather/sharding_constraint")
                     for _, dims in shapes if dims[1:] == (MF_RANK,))
    assert factors == half_steps
    for side in ("row", "col"):
        written = sorted(slots for slots, name in mf4["scalar_gathers"]
                         if f"mf/mf/{side}/gather/" in name)
        own = [e // 4 * cap for e, cap in mf4["lanes"][side]]
        # the offsets and the other side's index, a scalar gather each a bucket
        assert written == sorted(own * 2 * MF_ALTERNATIONS)


@pytest.mark.parametrize("devices, build, exchanges", [
    (None, _program, 0), (1, _program, 0), (4, _program, len(RE)),
    (None, _mf_program, 0), (4, _mf_program, 2 * MF_ALTERNATIONS)])
def test_the_counter_reads_the_exchanges_a_trace_of_the_step_made(
        host, devices, build, exchanges):
    mesh = _mesh(devices)
    packed, mf = (_packed(host, mesh), None) if build is _program else (None, _mf_packed(host))
    program = build(mesh)
    assert _traced_exchanges(program, _placed(program, mesh, host, packed, mf)) == exchanges


@pytest.mark.parametrize("devices, constrained", [(None, False), (1, False), (4, True)])
def test_only_a_program_of_several_devices_lowers_the_step_with_a_sharding_constraint(
        host, devices, constrained):
    """The one-chip cells' guarantee: nothing is emitted, the step is the
    parent's. And the text would show one: four devices' lowering does."""
    mesh = _mesh(devices)
    program = _program(mesh)
    placed = _placed(program, mesh, host, _packed(host, mesh))
    text = program._step.lower(*placed).as_text().lower()
    assert ("sharding_constraint" in text or "@sharding" in text) == constrained


def test_the_exchange_changes_no_bit_of_a_sweep(host, four, mesh4):
    """Four devices' ``step`` with the exchange and with it taken out: the same
    values gathered by the same indices, so tables, loss and line-search counts
    are equal bit for bit."""
    def sweep(exchange: bool):
        program = _program(mesh4) if exchange else _without_exchange(_program(mesh4))
        state, loss = program.step(*_placed(program, mesh4, host, four["packed"]))
        return state, np.asarray(loss), program.take_solver_counts()

    state, loss, counts = sweep(True)
    plain_state, plain_loss, plain_counts = sweep(False)
    assert np.array_equal(counts.array, plain_counts.array) and counts.rows == plain_counts.rows
    assert counts.counters()["line_searches"] > 0
    assert loss.tobytes() == plain_loss.tobytes()
    np.testing.assert_array_equal(np.asarray(state.fe_coefficients),
                                  np.asarray(plain_state.fe_coefficients))
    for t, _ in RE:
        np.testing.assert_array_equal(np.asarray(state.re_tables[t]),
                                      np.asarray(plain_state.re_tables[t]))


def test_four_devices_count_the_line_searches_one_device_counts(host, four):
    """One sweep from the same start on one device and on four: float32 sums
    taken in another order end a lane's solve an iteration earlier or later
    (measured, four against one: 1008 line searches against 1007, 1018 lanes'
    trials against 1016, 74 in lock step against 73, the fixed effect's 4)."""
    def counts(devices):
        mesh = _mesh(devices)
        program = _program(mesh)
        packed = four["packed"] if devices == 4 else _packed(host, mesh)
        _, loss = program.step(*_placed(program, mesh, host, packed))
        return program.take_solver_counts().counters(), float(loss)

    (on_four, loss_four), (on_one, loss_one) = counts(4), counts(1)
    for name in ("line_searches", "lane_trials", "lockstep_trials", "fe_trials",
                 "lockstep_iterations", "row_trials_wanted"):
        assert 0 < on_one[name]
        assert abs(on_four[name] - on_one[name]) <= max(1, 0.01 * on_one[name]), name
    # four devices pay for the lanes that fill a bucket up to a multiple of
    # four; the lanes that are there are the same lanes, counted once each
    assert on_one["row_trials_paid"] <= on_four["row_trials_paid"] <= (
        1.3 * on_one["row_trials_paid"])
    for t, _ in RE:
        assert on_four[f"re/{t}/lockstep_iterations"] > 0
    assert on_four["lane_solves"] == on_one["lane_solves"] == (
        len(np.unique(host["user"])) + len(np.unique(host["item"])))
    assert abs(loss_four - loss_one) < 1.3e-5 * loss_one


def test_on_four_devices_the_counts_are_taken_chip_by_chip_and_read_the_same(
        host, four, mesh4):
    """A bucket's counts taken over each chip's own lanes and brought together
    once (``_lane_parts`` = the mesh's four) against the same program counting
    every bucket over all its lanes at once: the same array to the digit, the
    padding lanes that fill a bucket up to a multiple of four in no sum."""
    def sweep(parts):
        program = _program(mesh4)
        assert program._lane_parts == 4
        program._lane_parts = parts
        program.step(*_placed(program, mesh4, host, four["packed"]))
        return program.take_solver_counts()

    by_chip, at_once = sweep(4), sweep(1)
    assert np.array_equal(by_chip.array, at_once.array) and by_chip.rows == at_once.rows
    lanes = {t: sum(row.lanes for row in by_chip.rows if row.coordinate == f"re/{t}")
             for t, _ in RE}
    solved = {t: sum(r["lane_solves"] for r in by_chip.table()
                     if r["coordinate"] == f"re/{t}") for t, _ in RE}
    assert solved == {t: len(np.unique(host[t])) for t, _ in RE}
    assert all(row.lanes % 4 == 0 for row in by_chip.rows if row.family == "re")
    assert any(lanes[t] > solved[t] for t, _ in RE)  # there ARE padding lanes


@pytest.mark.parametrize("compiled", ["glmix4", "mf4"])
def test_the_counts_cross_the_chips_once_a_sweep(compiled, request):
    """The compiled step holds ONE ``max`` and ONE ``sum`` of int32 counts
    across the chips, over all the buckets stacked (outside every scope, the
    step's own), where one a count a bucket stood: no collective under a
    coordinate's ``solve`` carries an int32."""
    compiled = request.getfixturevalue(compiled)
    buckets = sum(len(shapes) for shapes in compiled["lanes"].values()) * (
        MF_ALTERNATIONS if "row" in compiled["lanes"] else 1)
    counts = sorted((name.rsplit("/", 1)[-1], op, shapes)
                    for op, shapes, name in compiled["collectives"]
                    if any(dtype == "s32" for dtype, _ in shapes)
                    and "/gather/" not in name and "/scatter/" not in name)
    # max_iterations 10: a trip count and eleven slots of search trips; 9 sums
    assert counts == [("reduce_max", "all-reduce", [("s32", (buckets, 12))]),
                      ("reduce_sum", "all-reduce", [("s32", (buckets, 9))])]


def test_the_variances_of_a_fit_on_the_mesh_go_through_the_same_exchange(host, four, mesh4):
    """``compute_state_variances`` indexes the offsets by every bucket's
    ``sample_rows`` too: once a coordinate they are brought whole to every
    chip, and the variances are those of the program that does not."""
    from photon_ml_tpu.parallel.distributed import compute_state_variances

    def variances(exchange: bool):
        program = _program(mesh4) if exchange else _without_exchange(_program(mesh4))
        _, _, state = _placed(program, mesh4, host, four["packed"])
        rng = np.random.default_rng(39)  # any coefficients: the offsets differ by row

        def drawn(x):
            return jax.device_put(
                0.3 * rng.standard_normal(x.shape).astype(x.dtype), x.sharding)

        state = state.replace(fe_coefficients=drawn(state.fe_coefficients),
                              re_tables={t: drawn(state.re_tables[t]) for t, _ in RE})
        counter = default_registry().counter(EXCHANGES_TRACED)
        before = counter.value
        _, by_type, _ = compute_state_variances(
            program, state, _dataset(host), four["packed"])
        return by_type, counter.value - before

    exchanged, made = variances(True)
    plain, none = variances(False)
    assert (made, none) == (len(RE), 0)
    for t, _ in RE:
        assert np.isfinite(np.asarray(exchanged[t])).any()
        np.testing.assert_array_equal(np.asarray(exchanged[t]), np.asarray(plain[t]))
