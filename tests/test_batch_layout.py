"""Where a dense feature block is put row-major for the Pallas GLM kernels
(``data/batch.in_kernel_layout``, PR 49), and where it is left as it lies.

A CPU keeps every array row-major, so the rule never moves anything here; the
cases that must move a block patch the two things the rule observes (the
default backend, the array's reported layout) and the copy it makes
(``_row_major``), and watch what it does with them. What the placement buys on a TPU is held by the
compiled text in tests/test_tpu_compile.py and on the chip by chip_smoke.py.
"""

import os
import subprocess
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.layout import Format, Layout

import photon_ml_tpu.data.batch as batch_mod
import photon_ml_tpu.ops.pallas_glm as kernel_mod
from photon_ml_tpu import estimators
from photon_ml_tpu.data.batch import LabeledPointBatch, in_kernel_layout
from photon_ml_tpu.data.sparse_batch import SparseLabeledPointBatch
from photon_ml_tpu.telemetry.registry import default_registry
from photon_ml_tpu.types import TaskType

ROWS = 64
COLUMN_MAJOR, ROW_MAJOR = (1, 0), (0, 1)


def _relayouts() -> int:
    return default_registry().counter(batch_mod.DENSE_RELAYOUTS).value


@pytest.fixture
def solves(monkeypatch):
    """The batches ``train_glm`` hands its solves, the solve itself left out
    (under a backend patched to ``tpu`` the kernel would ask for Mosaic)."""
    seen = []

    def solve(objective, opt, batch, w0, *rest):
        seen.append(batch)
        return types.SimpleNamespace(coefficients=w0, value=0.0, iterations=0)

    monkeypatch.setattr(estimators, "_jitted_path_solve", solve)
    return seen


@pytest.fixture
def a_tpu_that_keeps_blocks_column_major(monkeypatch):
    """What the rule observes, as a v5e gives it for a [400000, 2000] block:
    backend ``tpu``, the array column-major until it has been placed; and
    the copy it makes recorded (its result reads row-major)."""
    placed, calls = [], []

    def row_major(x):
        calls.append(x)
        out = x + 0  # another array object with the same values
        placed.append(out)
        return out

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(
        batch_mod, "_major_to_minor",
        lambda x: ROW_MAJOR if any(x is p for p in placed) else COLUMN_MAJOR)
    monkeypatch.setattr(batch_mod, "_row_major", row_major)
    return calls


def _dense(d, dtype=jnp.float32):
    rng = np.random.default_rng(d)
    x = jnp.asarray(rng.standard_normal((ROWS, d)), dtype)
    y = jnp.asarray(rng.integers(0, 2, ROWS), jnp.float32)
    return x, y


def _sparse(d):
    rng = np.random.default_rng(d)
    rows = np.repeat(np.arange(ROWS), 3)
    cols = rng.integers(0, d, rows.size)
    return SparseLabeledPointBatch.from_coo(
        rows, cols, rng.standard_normal(rows.size).astype(np.float32),
        rng.integers(0, 2, ROWS).astype(np.float32), dim=d)


LEFT_AS_IT_LIES = [
    # (what is built, whether the backend and the reported layout are patched)
    pytest.param(lambda: _dense(256), False, id="cpu-already-row-major"),
    pytest.param(lambda: _sparse(256), True, id="sparse-batch"),
    pytest.param(lambda: _dense(256 + 128), True, id="beyond-MAX_KERNEL_DIM"),
    pytest.param(lambda: _dense(16), True, id="narrow-n-by-16"),
    pytest.param(lambda: _dense(256, jnp.int32), True, id="integer-block"),
]


@pytest.mark.parametrize("build,on_tpu", LEFT_AS_IT_LIES)
def test_create_and_train_glm_hand_back_the_same_features(
        build, on_tpu, request, monkeypatch, solves):
    """In every case but the one the rule names, ``create`` and ``train_glm``
    pass on the SAME object and count no relayout."""
    built = build()
    calls = []
    if on_tpu:
        calls = request.getfixturevalue("a_tpu_that_keeps_blocks_column_major")
        monkeypatch.setattr(batch_mod, "MAX_KERNEL_DIM", 256)
    before = _relayouts()
    if isinstance(built, SparseLabeledPointBatch):
        batch = built
        leaves = jax.tree_util.tree_leaves(batch)
        assert all(in_kernel_layout(leaf) is leaf for leaf in leaves)
    else:
        x, y = built
        assert in_kernel_layout(x) is x
        batch = LabeledPointBatch.create(x, y)
        assert batch.features is x
    estimators.train_glm(batch, TaskType.LOGISTIC_REGRESSION,
                         regularization_weights=[1.0, 10.0])
    assert len(solves) == 2
    for solved in solves:
        for got, given in zip(jax.tree_util.tree_leaves(solved),
                              jax.tree_util.tree_leaves(batch)):
            assert got is given
    assert not calls and _relayouts() == before


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("host_rows", [False, True], ids=["device-array", "host-rows"])
def test_a_column_major_block_is_placed_once_in_the_kernels_layout(
        a_tpu_that_keeps_blocks_column_major, dtype, host_rows):
    calls = a_tpu_that_keeps_blocks_column_major
    x, _ = _dense(2000 // 8, jnp.dtype(dtype))  # 250 columns: 256 lanes, +2.4 %
    given = np.asarray(x) if host_rows else x
    before = _relayouts()
    placed = in_kernel_layout(given)
    assert placed is not given and len(calls) == 1
    moved, = calls
    assert isinstance(moved, jax.Array) and moved.sharding == x.sharding
    assert np.array_equal(np.asarray(moved), np.asarray(x))
    assert host_rows or moved is x
    assert _relayouts() == before + 1
    gauge = default_registry().gauge(batch_mod.DENSE_RELAYOUT_BYTES).value
    assert gauge == ROWS * 256 * jnp.dtype(dtype).itemsize  # whole lanes
    # a second pass over its result moves nothing
    assert in_kernel_layout(placed) is placed
    assert len(calls) == 1 and _relayouts() == before + 1


def test_a_copy_that_does_not_report_the_kernels_layout_is_refused_by_name(
        a_tpu_that_keeps_blocks_column_major, monkeypatch):
    """What a cache-loaded identity hands back under jax 0.9.0: the next
    ``jit`` would read it transposed or refuse its size, so the rule raises."""
    monkeypatch.setattr(batch_mod, "_row_major", lambda x: x + 0)  # never recorded as placed
    x, _ = _dense(250)
    before = _relayouts()
    with pytest.raises(RuntimeError, match=r"in_kernel_layout: a \(64, 250\) float32 block"):
        in_kernel_layout(x)
    assert _relayouts() == before


def test_the_copy_is_a_jitted_identity_that_is_never_written_to_the_compile_cache(
        monkeypatch):
    """``_row_major`` asks ``jit`` for ``Format(Layout((0, 1)), the array's own
    sharding)`` as ``out_shardings`` and compiles under a cache-write floor no
    compile reaches (a program LOADED from the persistent cache hands back
    arrays that report the default layout: PERF.md 6, PR 49), then puts the
    floor back; its result holds the block's values, committed."""
    floor = "jax_persistent_cache_min_compile_time_secs"
    before = getattr(jax.config, floor)
    seen = []
    real_jit = jax.jit

    def jit(fn, **kwargs):
        seen.append((kwargs, getattr(jax.config, floor)))
        return real_jit(fn, **kwargs)

    monkeypatch.setattr(jax, "jit", jit)
    x, _ = _dense(250)
    placed = batch_mod._row_major(x)
    (kwargs, floor_inside), = seen
    assert kwargs == {"out_shardings": Format(Layout(major_to_minor=ROW_MAJOR), x.sharding)}
    assert floor_inside == float("inf") and getattr(jax.config, floor) == before
    assert placed is not x and placed.committed and np.array_equal(placed, x)
    assert batch_mod._major_to_minor(placed) == ROW_MAJOR


#: run twice over ONE persistent compile cache with every compile written to it,
#: as ``benchmark/run.py`` sets it: the second process LOADS what the first compiled.
#: Column-major stands in for "a layout the platform does not default to" on a CPU.
_TWO_PROCESSES = """
import sys
import jax, jax.numpy as jnp
from jax.experimental.layout import Format, Layout
jax.config.update("jax_compilation_cache_dir", sys.argv[1])
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
import photon_ml_tpu.data.batch as batch_mod
batch_mod.KERNEL_LAYOUT = Layout(major_to_minor=(1, 0))
x = jnp.arange(64 * 200, dtype=jnp.float32).reshape(64, 200)
double = jax.jit(lambda a: a * 2)
ours = batch_mod._row_major(x)
jaxs = jax.device_put(x, Format(batch_mod.KERNEL_LAYOUT, x.sharding))
print(batch_mod._major_to_minor(ours), bool(jnp.array_equal(double(ours), 2 * x)),
      batch_mod._major_to_minor(jaxs), bool(jnp.array_equal(double(jaxs), 2 * x)))
"""


def test_a_placed_block_reports_its_layout_in_a_process_that_loads_its_programs(tmp_path):
    """Why ``_row_major`` is not ``jax.device_put``: under jax 0.9.0 an
    executable LOADED from the persistent compile cache hands back arrays that
    report the platform's default layout whatever they hold, and ``jit``
    compiles the next program for the report: on a CPU it then reads the block
    transposed, in silence; on the chip the second run of a cell died of a
    buffer-size mismatch (PERF.md 6, PR 49). The last two columns are the
    control: when they read ``(1, 0) True`` in the second process too, jax has
    mended it and ``_row_major`` can be ``jax.device_put`` again."""
    def run():
        done = subprocess.run(
            [sys.executable, "-c", _TWO_PROCESSES, str(tmp_path)], text=True,
            capture_output=True, timeout=300, check=True,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            env={**os.environ, "JAX_PLATFORMS": "cpu"})
        return done.stdout.strip().splitlines()[-1]

    assert run() == "(1, 0) True (1, 0) True"  # every program compiled here
    assert run() == "(1, 0) True (0, 1) False"  # every cached program loaded
    assert not [f.name for f in tmp_path.iterdir() if "as_the_kernels_read_it" in f.name]


def test_create_places_and_train_glm_finds_nothing_left_to_do(
        a_tpu_that_keeps_blocks_column_major, solves):
    calls = a_tpu_that_keeps_blocks_column_major
    x, y = _dense(250)
    before = _relayouts()
    batch = LabeledPointBatch.create(x, y)
    assert batch.features is not x and len(calls) == 1
    estimators.train_glm(batch, TaskType.LOGISTIC_REGRESSION,
                         regularization_weights=[0.1, 1.0, 10.0])
    assert [solved.features is batch.features for solved in solves] == [True] * 3
    assert len(calls) == 1 and _relayouts() == before + 1


def test_train_glm_places_a_bare_batch_once_a_fit_not_once_a_solve(
        a_tpu_that_keeps_blocks_column_major, solves):
    """A batch built by the bare constructor, which places nothing: the fit
    pays ONE relayout, every λ reads the placed block."""
    calls = a_tpu_that_keeps_blocks_column_major
    x, y = _dense(250)
    batch = LabeledPointBatch(features=x, labels=y, offsets=jnp.zeros(ROWS),
                              weights=jnp.ones(ROWS))
    before = _relayouts()
    estimators.train_glm(batch, TaskType.LOGISTIC_REGRESSION,
                         regularization_weights=[0.1, 1.0, 10.0, 100.0])
    assert len(calls) == 1 and _relayouts() == before + 1
    assert len({id(solved.features) for solved in solves}) == 1
    assert solves[0].features is not x and batch.features is x
    assert all(solved.labels is y for solved in solves)


def test_the_rule_is_the_auto_rules_own_predicate():
    """Written once, in data/batch.py; ops/pallas_glm.py, where
    ``GLMObjective._pallas_enabled`` imports it from, hands back the object."""
    assert kernel_mod.kernel_supports is batch_mod.kernel_supports
    assert kernel_mod.MAX_KERNEL_DIM is batch_mod.MAX_KERNEL_DIM
    assert batch_mod.kernel_supports(batch_mod.MAX_KERNEL_DIM)
    assert not batch_mod.kernel_supports(batch_mod.MAX_KERNEL_DIM + 1)
    # whole lanes may cost an eighth: 2,000 -> 2,048 passes, 16 -> 128 does not
    assert 2048 <= batch_mod.MAX_ROW_MAJOR_GROWTH * 2000
    assert 128 > batch_mod.MAX_ROW_MAJOR_GROWTH * 16


def test_a_traced_block_is_left_to_the_program(a_tpu_that_keeps_blocks_column_major):
    """Under ``jit`` the block is a tracer: its layout is the compiler's."""
    calls = a_tpu_that_keeps_blocks_column_major
    x, _ = _dense(250)
    seen = []
    jax.make_jaxpr(lambda a: seen.append(in_kernel_layout(a) is a) or a)(x)
    assert seen == [True] and not calls
