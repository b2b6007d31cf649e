"""The program's own record of what it compiled, and the scopes that stand in
it (``telemetry/program_ledger.compiled_scopes``; PERF.md §3).

Held here: (a) a tiny GLMix + factorization step compiled on the CPU carries
every phase's ``jax.named_scope`` in its instructions' ``op_name``, the
coordinate-descent path's bucket solves carry ``gather`` / ``solve`` /
``scatter`` and the λ path's program the three ``lbfgs/`` scopes; (b) a scope
is never an instruction: the optimized HLO of the step, metadata and source
tables stripped, is the same bytes with ``jax.named_scope`` patched to a null
context; (c) the wrapper keeps a signature on a call that traces and nothing
on one that dispatches; (d) the parse reads a name, a signature and a name
the profiler cut short.
"""

import contextlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import step_scopes
from photon_ml_tpu.algorithm.coordinates import (
    CoordinateOptimizationConfig,
    RandomEffectCoordinate,
)
from photon_ml_tpu.algorithm.mf_coordinate import (
    MatrixFactorizationCoordinate,
    build_mf_dataset,
)
from photon_ml_tpu.data.batch import LabeledPointBatch
from photon_ml_tpu.data.game_data import (
    build_game_dataset,
    build_random_effect_dataset,
)
from photon_ml_tpu.estimators import train_glm
from photon_ml_tpu.optim.optimizer import OptimizerConfig, OptimizerType
from photon_ml_tpu.parallel.distributed import (
    FixedEffectStepSpec,
    GameTrainProgram,
    MatrixFactorizationStepSpec,
    RandomEffectStepSpec,
)
from photon_ml_tpu.telemetry import program_ledger
from photon_ml_tpu.telemetry.program_ledger import (
    compiled_scopes,
    ledger_jit,
    parse_instruction,
    scopes_of_text,
)
from photon_ml_tpu.types import TaskType

TASK = TaskType.LOGISTIC_REGRESSION
RE_TYPES = ("user", "item")
OPT = OptimizerConfig(optimizer_type=OptimizerType.LBFGS, max_iterations=4,
                      rel_function_tolerance=1e-6)


def tiny_data():
    """(dataset, random-effect buckets, factorization buckets) of a tiny fit"""
    rng = np.random.default_rng(7)
    n = 160
    keys = {"user": np.array([f"u{i}" for i in rng.zipf(1.6, size=n) % 12]),
            "item": np.array([f"i{i}" for i in rng.integers(0, 6, size=n)])}
    x_re = rng.normal(size=(n, 3))
    x_re[:, 0] = 1.0
    dataset = build_game_dataset(
        labels=(rng.uniform(size=n) < 0.5).astype(np.float32),
        feature_shards={"global": rng.normal(size=(n, 6)),
                        "side": rng.normal(size=(n, 2)), "re": x_re},
        entity_keys=keys, dtype=np.float32,
    )
    re_datasets = {t: build_random_effect_dataset(
        dataset, t, "re", bucket_sizes=(8, 128)) for t in RE_TYPES}
    mf_datasets = {"mf": build_mf_dataset(dataset, "user", "item",
                                          bucket_sizes=(8, 128))}
    return dataset, re_datasets, mf_datasets


def one_fused_step(inputs):
    """A GLMix + factorization program with an extra fixed effect (every kind
    of coordinate the step knows), after one sweep; whoever holds it keeps
    the label's record alive."""
    program = GameTrainProgram(
        TASK,
        FixedEffectStepSpec("global", OPT, l2_weight=0.5),
        tuple(RandomEffectStepSpec(t, "re", OPT, l2_weight=1.0)
              for t in RE_TYPES),
        mf_specs=(MatrixFactorizationStepSpec(
            "mf", "user", "item", 2, OPT, l2_weight=1.0),),
        extra_fes=(FixedEffectStepSpec("side", OPT, l2_weight=0.5),),
    )
    data, buckets = program.prepare_inputs(*inputs)
    program.step(data, buckets, program.init_state(*inputs))
    return program, buckets


def scope_paths(label: str) -> set:
    """The scopes of every instruction compiled under the label: ``op_name``
    less its last component, the primitive."""
    record = compiled_scopes(label)
    assert record is not None, label
    return {op_name.rpartition("/")[0]
            for _, op_name in record.instructions.values()}


def holds(paths: set, *scopes: str) -> bool:
    """Some instruction stands under all the scopes, in that order, wherever
    each stands in its ``op_name`` (after ``vmap(``, under ``while/body``)."""
    pattern = re.compile(".*".join(
        r"(?<![^/(])" + re.escape(scope) + r"(?![^/)])" for scope in scopes))
    return any(pattern.search(path) for path in paths)


@pytest.fixture(scope="module")
def inputs():
    return tiny_data()


@pytest.fixture(scope="module")
def game(inputs):
    return one_fused_step(inputs)


@pytest.fixture(scope="module")
def step_text(game):
    """The compiled step's text as ``compiled_scopes`` parsed it (the only
    way to a program's text), taken on the label's first asking."""
    texts = []
    parse = program_ledger.scopes_of_text
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(program_ledger, "scopes_of_text",
                      lambda text: texts.append(text) or parse(text))
        assert compiled_scopes("train/step") is not None
    return texts[0]


@pytest.fixture(scope="module")
def step_paths(step_text):
    return scope_paths("train/step")


STEP_SCOPES = [
    ("score/global",), ("score/side",), ("score/user",), ("score/item",),
    ("score/mf",), ("residual",), ("loss",), ("fe/solve",),
    ("fe/solve", "lbfgs/direction"), ("fe/solve", "lbfgs/history"),
    ("fe/solve", "lbfgs/line_search"), ("extra_fe/side/solve",),
    ("extra_fe/side/solve", "lbfgs/line_search"),
    *[(f"re/{t}", phase) for t in RE_TYPES
      for phase in ("gather", "solve", "scatter")],
    *[(f"re/{t}", "solve", lbfgs) for t in RE_TYPES
      for lbfgs in ("lbfgs/direction", "lbfgs/history", "lbfgs/line_search")],
    *[(f"mf/mf/{side}", phase) for side in ("row", "col")
      for phase in ("gather", "solve", "scatter")],
    *[(f"mf/mf/{side}", "solve", "lbfgs/line_search") for side in ("row", "col")],
]


@pytest.mark.parametrize("scopes", STEP_SCOPES, ids="/".join)
def test_the_fused_step_carries_the_scope(step_paths, scopes):
    assert holds(step_paths, *scopes), sorted(step_paths)[:40]


def test_the_step_record_names_its_entry_loops_and_their_signatures(game):
    record = compiled_scopes("train/step")
    assert record is compiled_scopes("train/step")  # parsed once a process
    # one solver loop for each fixed effect and each bucket solved
    _, buckets = game
    solved = 2 + sum(len(buckets[t]) for t in RE_TYPES) + sum(
        len(side) for side in buckets["__mf__"]["mf"].values())
    assert len(record.entry_loops) >= solved
    for name in record.entry_loops:
        signature, op_name = record.instructions[name]
        assert signature.endswith(")while") and op_name.endswith("/while")
    # a re-score is not filed under the solve before it, nor under ``re/``
    assert not holds({p for p in scope_paths("train/step") if "score/user" in p},
                     "re/user")


def test_every_scope_of_the_step_falls_to_the_category_its_table_names(step_text):
    """What ties the program's scope names to the benchmark's rules
    (``benchmark/step_scopes.RULES``; PERF.md §3's table): a scope renamed
    on one side alone would move its seconds to ``lane_update`` or
    ``unscoped`` with the seven shares still summing to the step's."""
    op_names = [op for _, op in compiled_scopes("train/step").instructions.values()]
    filed = {(step_scopes.category(op), step_scopes.phase(op)) for op in op_names}
    assert {cat for cat, _ in filed} == set(step_scopes.CATEGORIES)
    # the three scopes no rule names split the lanes' update and the fixed
    # effects' solve in the printed table
    assert {phase for cat, phase in filed if cat == "lane_update"} >= {
        "lbfgs/direction", "lbfgs/history", "solve"}
    assert {phase for cat, phase in filed if cat == "fe"} == {
        "lbfgs/direction", "lbfgs/history", "lbfgs/line_search", "solve"}
    assert {phase for cat, phase in filed if cat == "lane_search"} == {
        "lbfgs/line_search"}
    expected = {
        "re/user/gather": "gather", "re/item/gather": "gather",
        "mf/mf/row/gather": "gather", "mf/mf/col/gather": "gather",
        "re/user/scatter": "score_scatter", "mf/mf/col/scatter": "score_scatter",
        "score/global": "score_scatter", "score/side": "score_scatter",
        "score/user": "score_scatter", "score/mf": "score_scatter",
        "fe/solve": "fe", "extra_fe/side/solve": "fe",
        "residual": "residual", "loss": "residual",
    }
    for scope, cat in expected.items():
        under = {step_scopes.category(op) for op in op_names
                 if holds({op.rpartition("/")[0]}, scope)}
        assert under == {cat}, (scope, under)
    # nothing a phase's scope stands over is unscoped: what is, is the
    # step's own glue (its arguments, the state's tuple, the counts' sum)
    scoped = re.compile(step_scopes._scope(
        "re", "mf", "fe/solve", "extra_fe", "score", "residual", "loss"))
    for op in op_names:
        if step_scopes.category(op) == "unscoped":
            assert not scoped.search(op.rpartition("/")[0]), op


def test_the_benchmarks_copy_of_the_parse_reads_what_the_programs_does(step_text):
    """``mf_time_share_pct`` keeps a copy of the parse until a ``benchmark``
    PR points it at ``compiled_scopes`` (ROADMAP R7 k): held to agree on
    every instruction of the compiled step, whole and cut short as the
    profiler cuts an event's name, so that the copies cannot drift."""
    from benchmark.layer_metrics import mf_time_share_pct as copy

    lines = [line for line, _ in program_ledger._INSTRUCTION.findall(step_text)]
    assert len(lines) > 1000
    assert lines == [line for line, _ in copy._INSTRUCTION.findall(step_text)]
    for line in lines:
        for text in (line, line[:40], line[:120], line[:len(line) // 2]):
            assert parse_instruction(text) == copy._parse(text), text
    record = scopes_of_text(step_text)
    names, loops = copy.scoped_instructions(step_text)
    assert names and names == {
        name: sig for name, (sig, op) in record.instructions.items()
        if copy.SCOPE.search(op)}
    assert loops and loops == {
        name for name in record.entry_loops if name in names}


@pytest.fixture(scope="module")
def coordinate_descent_paths(inputs):
    """One update of a random-effect and of a factorization coordinate on
    the coordinate-descent path, each bucket its own ``coord/*`` program."""
    dataset, re_datasets, mf_datasets = inputs
    config = CoordinateOptimizationConfig(optimizer=OPT, l2_weight=1.0)
    user = RandomEffectCoordinate("user", dataset, re_datasets["user"], TASK, config)
    user.update_model(user.initial_model())
    mf = MatrixFactorizationCoordinate(
        coordinate_id="mf", dataset=dataset, mf_dataset=mf_datasets["mf"],
        task=TASK, config=config, num_latent_factors=2, num_alternations=1)
    mf.update_model(mf.initial_model())
    return {label: scope_paths(label)
            for label in ("coord/re_bucket_solve", "coord/mf_side_solve")}


@pytest.mark.parametrize("label", ["coord/re_bucket_solve", "coord/mf_side_solve"])
@pytest.mark.parametrize("phase", ["gather", "solve", "scatter"])
def test_the_coordinate_descent_path_carries_the_bucket_phases(
        coordinate_descent_paths, label, phase):
    paths = coordinate_descent_paths[label]
    assert holds(paths, phase)
    assert holds(paths, "solve", "lbfgs/line_search")
    assert not holds(paths, "re/user")  # the coordinate's scope is the fused step's


@pytest.mark.parametrize("scope", ["lbfgs/direction", "lbfgs/history",
                                   "lbfgs/line_search"])
def test_the_lambda_path_carries_the_solver_scopes(scope):
    rng = np.random.default_rng(0)
    x = rng.normal(size=(64, 5)).astype(np.float32)
    y = (rng.random(64) < 0.5).astype(np.float32)
    train_glm(LabeledPointBatch.create(jnp.asarray(x), jnp.asarray(y)),
              TASK, optimizer=OPT,
              regularization_weights=(1.0,))
    paths = scope_paths("glm/path_solve")
    assert holds(paths, scope)
    assert not holds(paths, "re/user") and not holds(paths, "fe/solve")


def _stripped(text: str) -> str:
    """Optimized HLO less everything a scope may touch: the instructions'
    metadata and the tables of files, functions and frames above them."""
    head, _, rest = text.partition("\nFileNames\n")
    body = re.sub(r"\A.*?\nStackFrames\n(?:\d+ [^\n]*\n)*", "", "\n" + rest,
                  flags=re.DOTALL) if rest else ""
    return re.sub(r", metadata=\{[^}]*\}", "", head + "\n" + body)


def test_a_scope_is_never_an_instruction(inputs, monkeypatch):
    texts = []  # what compiled_scopes parsed: the only way to a program's text
    parse = program_ledger.scopes_of_text
    monkeypatch.setattr(program_ledger, "scopes_of_text",
                        lambda text: texts.append(text) or parse(text))

    def text_of_a_fresh_program():
        held = one_fused_step(inputs)
        assert compiled_scopes("train/step") is not None and held
        return texts[-1]

    scoped = text_of_a_fresh_program()
    assert 'op_name="jit(_step_impl)/re/user/gather' in scoped
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare = text_of_a_fresh_program()
    assert len(texts) == 2 and "re/user" not in bare and "lbfgs/" not in bare
    assert _stripped(bare) == _stripped(scoped)
    assert _stripped(scoped).count("\n") > 1000


def test_a_dispatch_that_does_not_trace_keeps_nothing():
    @ledger_jit(label="test/compiled_scopes/f", static_argnums=(0,))
    def f(k, x):
        with jax.named_scope("phase/a"):
            return x * k

    assert compiled_scopes("test/compiled_scopes/f") is None
    f(2, jnp.ones(4, jnp.float32))  # traces
    kept = program_ledger._TRACED["test/compiled_scopes/f"]
    # the signature is abstract (no array is held), the static as it is
    assert kept.args == (2, jax.ShapeDtypeStruct((4,), jnp.float32))
    first = compiled_scopes("test/compiled_scopes/f")
    assert first.instructions and all(
        "phase/a" in op for _, op in first.instructions.values()
        if op.startswith("jit("))
    f(2, jnp.zeros(4, jnp.float32))  # dispatches
    assert program_ledger._TRACED["test/compiled_scopes/f"] is kept
    assert compiled_scopes("test/compiled_scopes/f") is first
    f(2, jnp.ones(8, jnp.float32))  # another shape: traces, and the record follows it
    assert compiled_scopes("test/compiled_scopes/f") is not first
    assert program_ledger._TRACED["test/compiled_scopes/f"].args[1].shape == (8,)
    # inlined in an outer trace it is no program of its own: nothing is kept
    kept = program_ledger._TRACED["test/compiled_scopes/f"]
    jax.jit(lambda x: f(3, x))(jnp.ones(4, jnp.float32))
    f(2, jnp.ones(8, jnp.float32))
    assert program_ledger._TRACED["test/compiled_scopes/f"] is kept
    del f  # the record goes with its program
    assert compiled_scopes("test/compiled_scopes/f") is None


def test_a_weak_typed_leaf_is_remembered_as_one():
    """A Python scalar's array is weak-typed and lowers by that: remembered
    as strong it would lower as ANOTHER program than the one that ran."""
    @ledger_jit(label="test/compiled_scopes/weak")
    def f(x, scale):
        return x * scale

    x = jnp.ones(4, jnp.bfloat16)
    weak = jnp.asarray(2.0)
    assert weak.weak_type and f(x, weak).dtype == jnp.bfloat16
    kept = program_ledger._TRACED["test/compiled_scopes/weak"]
    assert kept.args[1].weak_type and not kept.args[0].weak_type
    assert f.lower(*kept.args).out_info.dtype == jnp.bfloat16
    traces = []
    g = ledger_jit(lambda x, scale: traces.append(1) or x * scale,
                   label="test/compiled_scopes/weak2")
    g(x, weak)
    assert compiled_scopes("test/compiled_scopes/weak2") is not None
    assert traces == [1]  # jit's own cached lowering answered: no retrace


def test_a_committed_leaf_is_remembered_in_the_layout_it_lies_in():
    """``jit`` compiles for the layout a committed array has (a dense batch
    placed by ``data/batch.in_kernel_layout``): remembered by its sharding
    alone it would lower as the DEFAULT-layout program, another than the one
    that ran, and every partition by its record would be nothing (the TRON
    cell's ``path_hv_roofline`` fell silent so; chip run, PR 49). Column-major
    stands in, on a CPU, for a layout the platform does not default to."""
    from jax.experimental.layout import Format, Layout

    traces = []
    f = ledger_jit(lambda x, w: traces.append(1) or (x @ w, w + 1),
                   label="test/compiled_scopes/layout", donate_argnums=(1,))
    x = jax.device_put(jnp.ones((8, 4), jnp.float32), jax.devices()[0])
    lies = Format(Layout(major_to_minor=(1, 0)), x.sharding)
    x = jax.jit(lambda a: a, out_shardings=lies)(x)
    w = jax.device_put(jnp.ones((4, 4), jnp.float32), jax.devices()[0])
    f(x, w)
    kept = program_ledger._TRACED["test/compiled_scopes/layout"]
    assert kept.args[0].format.layout.major_to_minor == (1, 0)
    # donated and deleted by the call: its sharding, no layout to report
    assert w.is_deleted() and kept.args[1].sharding == x.sharding
    assert compiled_scopes("test/compiled_scopes/layout") is not None
    assert traces == [1]  # jit's own cached lowering answered: the SAME program
    compiled = f.lower(*kept.args).compile()
    x_format = jax.tree_util.tree_leaves(compiled.input_formats)[0]
    assert x_format.layout.major_to_minor == (1, 0)


def test_only_a_trace_inside_the_call_is_remembered():
    @ledger_jit(label="test/compiled_scopes/own")
    def f(x):
        return x + 1

    # a lowering outside any dispatch traces, and is no call of the label's
    f.lower(jax.ShapeDtypeStruct((3,), jnp.float32))
    assert compiled_scopes("test/compiled_scopes/own") is None
    f(jnp.ones(5, jnp.float32))  # traces
    kept = program_ledger._TRACED["test/compiled_scopes/own"]
    f.lower(jax.ShapeDtypeStruct((7,), jnp.float32))  # traces, outside
    f(jnp.ones(5, jnp.float32))  # dispatches: the lowering's trace is not its
    assert program_ledger._TRACED["test/compiled_scopes/own"] is kept
    assert kept.args[0].shape == (5,)


def test_a_dispatch_beside_another_threads_trace_keeps_nothing():
    """One wrapper serves every thread (serving's ``serve/score``): a call
    that dispatches while another thread's call traces is not the call that
    traced, and its arguments are not the program's."""
    import threading

    tracing, dispatched = threading.Event(), threading.Event()

    @ledger_jit(label="test/compiled_scopes/threads")
    def f(x):
        if x.shape == (6,):  # the second shape's trace waits for the dispatch
            tracing.set()
            assert dispatched.wait(60)
        return x * 2

    f(jnp.ones(3, jnp.float32))  # traces: (3,) is compiled
    kept = program_ledger._TRACED["test/compiled_scopes/threads"]
    tracer = threading.Thread(target=f, args=(jnp.ones(6, jnp.float32),))
    tracer.start()
    assert tracing.wait(60)
    f(jnp.zeros(3, jnp.float32))  # dispatches while the other thread traces
    assert program_ledger._TRACED["test/compiled_scopes/threads"] is kept
    dispatched.set()
    tracer.join(60)
    assert program_ledger._TRACED["test/compiled_scopes/threads"].args[0].shape == (6,)


def test_an_unknown_label_reads_as_nothing():
    assert compiled_scopes("no/such/label") is None


HLO = '''HloModule jit_f, entry_computation_layout={(f32[8]{0})->f32[8]{0}}

%body.1 (p: (s32[], f32[8])) -> (s32[], f32[8]) {
  %p = (s32[], f32[8]{0}) parameter(0)
  %copy.7 = f32[8]{0} copy(%gte.2)
  ROOT %fusion.3 = (s32[], f32[8]{0}) fusion(%p), kind=kLoop, calls=%fused, metadata={op_name="jit(f)/re/user/solve/vmap()/while/body/mul" source_file="a.py" source_line=3}
}

ENTRY %main.9 (x: f32[8]) -> f32[8] {
  %x = f32[8]{0} parameter(0), metadata={op_name="x"}
  %while.4 = (s32[], /*index=1*/f32[8]{0:T(8,128)}) while(%tuple.2), condition=%cond.1, body=%body.1, metadata={op_name="jit(f)/re/user/solve/vmap()/while" source_file="a.py" source_line=2}
  %all-reduce.1 = f32[8]{0} all-reduce(%gte.5), to_apply=%add
  ROOT %gather_fusion = f32[8]{0} fusion(%all-reduce.1), kind=kLoop, calls=%g, metadata={op_name="jit(f)/score/user/gather"}
}
'''


def test_the_parse_reads_names_signatures_and_the_entry_loops():
    record = scopes_of_text(HLO)
    assert record.instructions == {
        "fusion.3": ("(s32[],f32[8])fusion",
                     "jit(f)/re/user/solve/vmap()/while/body/mul"),
        "x": ("f32[8]parameter", "x"),
        "while.4": ("(s32[],f32[8])while", "jit(f)/re/user/solve/vmap()/while"),
        "gather_fusion": ("f32[8]fusion", "jit(f)/score/user/gather"),
    }  # no metadata, no entry: the copy and the all-reduce
    assert record.entry_loops == {"while.4"}


@pytest.mark.parametrize("text, expected", [
    ("%while.4 = (s32[], /*index=1*/f32[8]{0:T(8,128)}) while(%tuple.2), body=%b",
     ("while.4", "(s32[],f32[8])while", True)),
    # a profiler's event name, cut inside the tuple of shapes: a prefix
    ("%while.4 = (s32[], /*index=1*/f32[8]{0:T(8",
     ("while.4", "(s32[],f32[8]", False)),
    ("%while.4 = (s32[], /*ind", ("while.4", "(s32[],", False)),
    ("%fusion.12 = f32[16,8]{1,0} fusion(f32[16]{0} %p)",
     ("fusion.12", "f32[16,8]fusion", True)),
])
def test_the_parse_of_one_instruction(text, expected):
    assert parse_instruction(text) == expected
    whole = parse_instruction(
        "%while.4 = (s32[], /*index=1*/f32[8]{0:T(8,128)}) while(%t)")[1]
    if not expected[2]:
        assert whole.startswith(expected[1])
