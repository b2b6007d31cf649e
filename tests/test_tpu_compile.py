"""Compiles for a DESCRIBED TPU v5e (no chip attached, nothing runs): what the
interpreter cannot show of the Pallas kernels, and what the benchmark's trace
reduction will find in the TRON path program.

Held here: both kernels compile through Mosaic at the widths the auto rule
admits (scoped VMEM, tiling) with their per-row columns a ``[3, n]`` block
(PR 53), and ``glm/path_solve`` under
TRON at the benchmark cell's size holds ONE custom call a product, inside the
CG loop, under ``tron/hv``, named after its jitted wrapper by a name
``benchmark/trace_reduce.KERNEL`` does not match, with X relaid out twice a
solve and never inside a loop WHERE IT ARRIVES AS THE PLATFORM LAYS IT, and
(PR 49) not at all where it arrives row-major, as ``data/batch.
in_kernel_layout`` places it: under L-BFGS, under TRON and in the λ grid's
program; and (PR 44) ``glm/path_solve`` over the sparse
cell's hybrid batch with its ELL view in width tiers compiles into a program
of ordinary size whose tiers are gathered and scattered under the two
``sparse/tail_*`` scopes, and (PR 45) whose L-BFGS history keeps every slot
as whole tiles, ``f32[10,157944,128]``, m an untiled major dimension; and
(PR 50) a random-effect bucket's Newton solve, whose batched Hessian is an MXU
contraction at precision ``highest``.

This is the ONE file that describes a topology: the TPU library is loaded by
the process that runs these tests, inside a fixture, never at import.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.layout import Format
from jax.sharding import SingleDeviceSharding

import photon_ml_tpu.ops.pallas_glm as kernel_mod
from benchmark import trace_reduce
from photon_ml_tpu.data.batch import KERNEL_LAYOUT, LabeledPointBatch
from photon_ml_tpu.ops.losses import LogisticLoss
from photon_ml_tpu.ops.objective import GLMObjective
from photon_ml_tpu.optim.optimizer import OptimizerConfig, OptimizerType

ROWS, FEATURES = 400_000, 2_000  # logistic-epsilon-tron.path


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or it logs under /tmp
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - whatever keeps the library away
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _shape(one_chip, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _gradient_call(one_chip, n, d, d_pad, dtype):
    return jax.jit(
        lambda x, aux, w: kernel_mod._fused_padded(LogisticLoss(), x, aux, False, w)
    ).lower(_shape(one_chip, (n, d), dtype), _shape(one_chip, (3, n)),
            _shape(one_chip, (d_pad,)))


def _product_call(one_chip, n, d, d_pad, dtype):
    return jax.jit(
        lambda x, aux, w, v, z: kernel_mod._hv_one_pass(
            LogisticLoss(), x, aux, False, w, v, z)
    ).lower(_shape(one_chip, (n, d), dtype), _shape(one_chip, (3, n)),
            _shape(one_chip, (d_pad,)), _shape(one_chip, (d_pad,)),
            _shape(one_chip, ()))


@pytest.mark.parametrize("d,rows_short", [(512, 0), (2000, 617), (4096, 0), (16384, 0)],
                         ids=["d512", "d2000-ragged", "d4096", "d16384"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("lower", [_gradient_call, _product_call],
                         ids=["gradient", "product"])
def test_the_kernels_compile_for_a_v5e(one_chip, lower, d, rows_short, dtype):
    """Both kernels through Mosaic with the aux block ``[3, n]`` in
    ``(3, tile)`` pieces, turned on the tile: scoped VMEM (the widest float32
    tile is 128 rows of 16,384, 8 MiB, twice buffered), tiling, the ragged
    last block along the lanes."""
    dtype = jnp.dtype(dtype)
    d_pad = kernel_mod._round_up(d, 128)
    n = 8 * kernel_mod._row_tile(d_pad, dtype.itemsize) - rows_short
    with jax.enable_x64(False):  # the suite's x64 makes the grid's index maps int64
        compiled = lower(one_chip, n, d, d_pad, dtype).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and f"f32[3,{n}]" in text


#: how X arrives: as the platform lays a [400000, 2000] block (column-major:
#: that pads nothing), or row-major, as ``data/batch.in_kernel_layout`` places it
AS_THE_PLATFORM_LAYS_IT, ROW_MAJOR = "platform", "row-major"
OPTIMIZERS = {
    "TRON": OptimizerConfig(OptimizerType.TRON, max_iterations=15, tolerance=1e-5,
                            max_cg_iterations=20),  # logistic-epsilon-tron.path
    "LBFGS": OptimizerConfig(OptimizerType.LBFGS, max_iterations=50,
                             rel_function_tolerance=1e-6),  # logistic-epsilon.path
}


def _dense_batch(one_chip, layout):
    """The dense cells' batch as shapes, X in the given layout."""
    placement = Format(KERNEL_LAYOUT, one_chip) if layout == ROW_MAJOR else one_chip
    return LabeledPointBatch(
        features=_shape(placement, (ROWS, FEATURES)), labels=_shape(one_chip, (ROWS,)),
        offsets=_shape(one_chip, (ROWS,)), weights=_shape(one_chip, (ROWS,)))


@pytest.fixture(scope="module")
def path_compiled(one_chip):
    """``glm/path_solve`` at the dense cells' size under one of their
    optimizers, the objective as ``train_glm`` builds it on a TPU; compiled
    once for each (optimizer, layout of X) a test asks for."""
    from photon_ml_tpu import estimators

    programs = {}

    def compiled(optimizer, layout):
        if (optimizer, layout) not in programs:
            with pytest.MonkeyPatch.context() as patch, jax.enable_x64(False):
                # what the auto rule and the interpreter's rule ask
                patch.setattr(jax, "default_backend", lambda: "tpu")
                programs[optimizer, layout] = estimators._jitted_path_solve.lower(
                    GLMObjective(LogisticLoss()), OPTIMIZERS[optimizer],
                    _dense_batch(one_chip, layout),
                    _shape(one_chip, (FEATURES,)), _shape(one_chip, ()), None, None,
                ).compile()
        return programs[optimizer, layout]

    return compiled


@pytest.fixture(scope="module")
def tron_path_text(path_compiled):
    """The optimized text of the TRON cell's program, X as the platform lays it."""
    return path_compiled("TRON", AS_THE_PLATFORM_LAYS_IT).as_text()


def _custom_calls(text):
    """{instruction name: op_name} of the program's Mosaic custom calls."""
    return {m.group(1): m.group(2) for m in re.finditer(
        r'%([\w.-]+) = [^\n]*custom_call_target="tpu_custom_call"[^\n]*'
        r'op_name="([^"]*)"', text)}


def test_a_product_is_one_custom_call_under_tron_hv_by_its_own_name(tron_path_text):
    calls = _custom_calls(tron_path_text)
    products = {name: op for name, op in calls.items() if "tron/hv" in op}
    assert len(products) == 1
    (name, op_name), = products.items()
    assert trace_reduce.instruction(f"%{name} = ") == "_hv_one_pass"
    assert not trace_reduce.KERNEL.search(name)
    assert op_name.endswith("tron/cg/while/body/tron/hv/jit(_hv_one_pass)/pallas_call")
    # the round's value and gradient: the gradient kernel, outside every scope
    others = {trace_reduce.instruction(f"%{n} = ") for n in calls if n not in products}
    assert others == {"_fused_padded"}
    assert not any("tron/" in calls[n] for n in calls if n not in products)


@pytest.mark.parametrize("optimizer", ["TRON", "LBFGS"])
def test_the_per_row_columns_reach_the_kernels_along_the_lanes(path_compiled, optimizer):
    """PR 53: a ``[rows, 3]`` float32 array is stored 128 lanes wide, 512 bytes
    a row in HBM and in every DMA of a launch. No instruction of a path
    program makes one; every kernel call takes ``f32[3,rows]`` beside X, X
    first, which is where ``benchmark/trace_reduce.kernel_operand`` reads the
    bytes ``sweeps_glm_kernel_roofline`` counts."""
    text = path_compiled(optimizer, ROW_MAJOR).as_text()
    assert not re.search(rf"= f32\[{ROWS},(3|128)\]", text)
    calls = re.findall(
        r"%([\w.-]+) = ([^\n]*custom_call_target=\"tpu_custom_call\"[^\n]*?\}\}),", text)
    # at the start and in the search (L-BFGS) or a round (TRON), and TRON's product
    assert len(calls) == (3 if optimizer == "TRON" else 2)
    for name, call in calls:
        operands = call.split("operand_layout_constraints=", 1)[1]
        assert f"f32[3,{ROWS}]" in operands and f"f32[{ROWS},3]" not in operands
        if trace_reduce.KERNEL.search(name):
            event = f"%{name} = {call}"[:trace_reduce.NAME_CHARS]  # as the trace names it
            assert trace_reduce.kernel_operand(event) == (ROWS, FEATURES, 4)
    assert any(trace_reduce.KERNEL.search(name) for name, _ in calls)


X_BLOCK = rf"f32\[{ROWS},{FEATURES}\]"
#: temporaries of a program that keeps no second X (one that relayouts it: 3.48 GB)
NO_SECOND_X = 0.5e9


def _copies_of_x(text):
    return re.findall(rf"= {X_BLOCK}[^ ]* copy\(", text)


def _x_format(compiled):
    """The format the compiled program takes its batch's features in."""
    return jax.tree_util.tree_leaves(compiled.input_formats)[0]


@pytest.mark.parametrize("optimizer", ["TRON", "LBFGS"])
@pytest.mark.parametrize("layout,copies", [(AS_THE_PLATFORM_LAYS_IT, 2), (ROW_MAJOR, 0)])
def test_x_is_read_by_the_kernels_alone_and_relaid_out_twice_a_solve(
        path_compiled, optimizer, layout, copies):
    """No XLA fusion reads the [rows, features] block (the jvp's two
    multiply-reduce passes and the hoisted third are gone). Where X arrives
    as the platform lays it, the relayout copy of X stands in ENTRY, before
    the first evaluation and before the rounds' loop: once a solve each,
    never once a product. Where it arrives row-major (PR 49) the program
    takes it in that layout, copies nothing and keeps no second X."""
    compiled = path_compiled(optimizer, layout)
    text = compiled.as_text()
    entry = text[text.index("\nENTRY "):]
    assert len(_copies_of_x(text)) == copies and len(_copies_of_x(entry)) == copies
    assert not re.search(rf"fusion\([^\n]*{X_BLOCK}", text)
    major_to_minor = _x_format(compiled).layout.major_to_minor
    temporaries = compiled.memory_analysis().temp_size_in_bytes
    if layout == ROW_MAJOR:
        assert major_to_minor == (0, 1) and temporaries < NO_SECOND_X
    else:
        assert major_to_minor == (1, 0) and temporaries > 3.2e9


# -- the sparse path program with its ELL view in width tiers (PR 44) ----------

#: logistic-kdda-sparse.path: rows, features, hot columns; the tiers the width
#: rule reads off the cell's counts (rows that hold a tier, its width) and the
#: flat overflow's length
SPARSE_ROWS, SPARSE_FEATURES, HOT_COLS = 525_484, 20_216_830, 2_048
SPARSE_TIERS = ((525_484, 8), (378_425, 3), (266_081, 3), (172_123, 3),
                (106_466, 3), (64_050, 3))
SPARSE_OVERFLOW = 239_245


@pytest.fixture(scope="module")
def sparse_path_compiled(one_chip):
    """``glm/path_solve`` over the cell's tiered hybrid batch, as shapes."""
    from photon_ml_tpu import estimators
    from photon_ml_tpu.data.sparse_batch import EllTier, SparseLabeledPointBatch
    from photon_ml_tpu.ops.sparse_objective import SparseGLMObjective

    def f32(*shape):
        return _shape(one_chip, shape)

    def i32(*shape):
        return _shape(one_chip, shape, jnp.int32)

    n, d = SPARSE_ROWS, SPARSE_FEATURES
    (_, first), further = SPARSE_TIERS[0], SPARSE_TIERS[1:]
    batch = SparseLabeledPointBatch(
        values=f32(SPARSE_OVERFLOW), col_indices=i32(SPARSE_OVERFLOW),
        row_ids=i32(SPARSE_OVERFLOW), labels=f32(n), offsets=f32(n),
        weights=f32(n), dim=d, ell_vals=f32(n, first), ell_cols=i32(n, first),
        ell_tiers=tuple(
            EllTier(vals=f32(w, rows), cols=i32(w, rows), row_ids=i32(rows))
            for rows, w in further),
        hot_vals=f32(n, HOT_COLS), hot_col_ids=i32(HOT_COLS))
    lbfgs = OptimizerConfig(OptimizerType.LBFGS, max_iterations=15,
                            rel_function_tolerance=1e-6, history=10)
    with jax.enable_x64(False):
        return estimators._jitted_path_solve.lower(
            SparseGLMObjective(LogisticLoss()), lbfgs, batch, f32(d), f32(),
            None, None,
        ).compile()


def test_the_tiered_sparse_path_compiles_small_and_fits_the_chip(sparse_path_compiled):
    """A further tier lies ``[width, n_k]``: kept ``[n_k, 4]`` this program
    was 245 MB of code (PERF.md 6, PR 44). And it fits a 16 GB chip."""
    memory = sparse_path_compiled.memory_analysis()
    assert memory.generated_code_size_in_bytes < 150e6
    assert (memory.argument_size_in_bytes + memory.temp_size_in_bytes
            + memory.output_size_in_bytes) < 15.75e9


def test_every_tier_is_gathered_and_scattered_under_the_tail_scopes(sparse_path_compiled):
    """The line search's body gathers and scatter-adds every tier and the
    overflow, each under ``sparse/tail_margins`` or ``sparse/tail_gradient``
    (what ``benchmark/path_sparse_scopes.py`` files under the tail), and
    nothing sparse stands outside a ``sparse/`` scope."""
    text = sparse_path_compiled.as_text()
    body = "line_search/while/body/"
    ops = re.findall(r'op_name="([^"]*' + body + r'[^"]*/(?:gather|scatter-add))"', text)
    blocks = len(SPARSE_TIERS) + 1  # and the flat overflow
    margins = {op for op in ops if "sparse/tail_margins/" in op}
    gradient = {op for op in ops if "sparse/tail_gradient/" in op}
    assert any(op.endswith("/gather") for op in margins)
    assert any(op.endswith("/scatter-add") for op in margins)  # at row ids
    assert any(op.endswith("/scatter-add") for op in gradient)
    assert any(op.endswith("/gather") for op in gradient)  # dzw[row_ids]
    scatters = re.findall(
        r"= f32\[" + str(SPARSE_FEATURES) + r"\][^\n]*" + body
        + r"sparse/tail_gradient/scatter-add", text)
    assert len(scatters) >= blocks
    assert all("sparse/" in op for op in ops)


# -- the same program's L-BFGS history: a slot is whole tiles (PR 45) ----------

HISTORY, SLOT_ROWS = 10, 157_944  # 8 * ceil(20,216,830 / 1,024): 20,216,832 floats
#: what commit 79e1951's program, its history ``f32[10,20216830]``, holds in
#: temporaries by the same compile (the change: 4,111,265,280)
PARENT_TEMPORARIES = 6_213_742_080


def test_a_history_slot_is_whole_tiles_read_and_shifted_under_its_scopes(
        sparse_path_compiled):
    """At d = 20,216,830 the history is ``[m, R, 128]`` with the tiles over
    its last two dimensions: slot k is one contiguous slab, where ``[m, d]``
    had m along the sublanes, ten rows stored as sixteen and every read of
    one row moving eight (PERF.md 6, PR 45). The recursion's fusions stand
    under ``lbfgs/direction`` and the shift under ``lbfgs/history``, where
    ``benchmark/path_sparse_scopes.py`` looks for them."""
    text = sparse_path_compiled.as_text()
    slot = rf"{SLOT_ROWS},128"
    assert f"f32[{HISTORY},{SLOT_ROWS},128]{{2,1,0:T(8,128)}}" in text
    assert f"f32[{HISTORY},{SPARSE_FEATURES}]" not in text
    # a visit reads one whole slot at the loop's counter, inside the recursion
    reads = re.findall(rf"= f32\[1,{slot}\]\{{2,1,0:T\(8,128\)\}} dynamic-slice\([^\n]*", text)
    assert len(reads) >= 4  # s and y, the backward loop and the forward one
    for line in reads:
        assert f"dynamic_slice_sizes={{1,{SLOT_ROWS},128}}" in line
        assert "lbfgs/direction/while/body" in line
    # the loops' own fusions (a dot and an update each) carry the scope
    fusions = dict(re.findall(
        r"%([\w.-]+) = [^\n]* fusion\([^\n]*op_name=\"([^\"]*)\"", text))
    in_loops = [op for op in fusions.values() if "lbfgs/direction/while/body" in op]
    assert len(in_loops) >= 4
    # the shift writes the whole history, under lbfgs/history, and nowhere else
    shifts = re.findall(
        rf"= f32\[{HISTORY},{slot}\][^ ]* fusion\([^\n]*op_name=\"([^\"]*)\"", text)
    assert len(shifts) == 2 and all("/lbfgs/history/" in op for op in shifts)
    # the folds: a pad of two floats behind a bitcast, never a [10, ...] block
    pads = re.findall(r"= f32\[20216832\][^ ]* pad\([^\n]*op_name=\"([^\"]*)\"", text)
    assert pads and all("/lbfgs/" in op for op in pads)


def test_the_slab_historys_temporaries_are_no_larger_than_the_row_forms(
        sparse_path_compiled):
    memory = sparse_path_compiled.memory_analysis()
    assert memory.temp_size_in_bytes <= PARENT_TEMPORARIES


# -- the λ grid's lanes: vmapped OWL-QN over ONE X (PR 47) ----------------------

GRID_LANES = 100  # logistic-epsilon-enet.grid


def _grid_solve(one_chip, layout):
    from photon_ml_tpu import estimators

    with jax.enable_x64(False):
        return estimators._jitted_grid_solve.lower(
            GLMObjective(LogisticLoss(), use_pallas=False), True, 10, 50, 1e-7, 1e-6,
            _dense_batch(one_chip, layout),
            _shape(one_chip, (GRID_LANES,)), _shape(one_chip, (GRID_LANES,)), None,
        ).compile()


@pytest.fixture(scope="module")
def grid_solve_compiled(one_chip):
    """``glm/grid_solve`` under OWL-QN at the cell's shapes, the objective as
    ``train_glm_grid`` builds it (``use_pallas=False``: the lanes are vmapped)."""
    return _grid_solve(one_chip, AS_THE_PLATFORM_LAYS_IT)


def test_the_grid_program_takes_a_row_major_x_as_it_lies(one_chip, grid_solve_compiled):
    """XLA reads any layout, and lays a relayout of its own in front of the
    lanes' products where X arrives column-major (one copy a solve); handed
    the block as ``LabeledPointBatch.create`` places it, it copies nothing."""
    assert len(_copies_of_x(grid_solve_compiled.as_text())) == 1
    placed = _grid_solve(one_chip, ROW_MAJOR)
    assert not _copies_of_x(placed.as_text())
    assert _x_format(placed).layout.major_to_minor == (0, 1)
    assert placed.memory_analysis().temp_size_in_bytes < NO_SECOND_X


def test_the_lanes_two_products_are_float32_matrix_products_under_glm_margins(
        grid_solve_compiled):
    """Under a lane axis over one X the margins and the gradient are true
    matrix products: two convolutions in the search loop's body, operands at
    precision ``highest`` (at the default a TPU rounds float32 operands to
    bfloat16), told apart by the transpose's wrapper round the one scope."""
    text = grid_solve_compiled.as_text()
    products = re.findall(
        r"= (f32\[[\d,]+\])[^\n]* convolution\([^\n]*operand_precision=\{(\w+),(\w+)\}"
        r'[^\n]*op_name="([^"]*)"', text)
    assert sorted(shape for shape, *_ in products) == [
        f"f32[{GRID_LANES},{FEATURES}]", f"f32[{GRID_LANES},{ROWS}]"]
    for shape, left, right, op_name in products:
        assert (left, right) == ("highest", "highest")
        assert "owlqn/line_search/while/body/" in op_name
        wrapper = ("transpose(jvp(glm/margins))" if shape.endswith(f",{FEATURES}]")
                   else "/jvp(glm/margins)")
        assert wrapper in op_name
    assert "tpu_custom_call" not in text
    # the lanes' shared start is ONE un-vmapped evaluation: a multiply-reduce
    assert 'vmap(jvp(glm/margins))/dot_general' in text
    memory = grid_solve_compiled.memory_analysis()
    assert memory.argument_size_in_bytes < 3.3e9 and memory.temp_size_in_bytes < 4.0e9


# -- the random effects' lanes under Newton: a batched Hessian (PR 50) --------------


def test_the_newton_lanes_hessian_is_an_mxu_contraction_at_the_highest_precision(one_chip):
    """A bucket of ``game-ymusic-r2.sweeps`` (the users' 4,576 lanes of 128
    rows) solved by Newton: the Hessian ``X_e' D X_e`` of every lane at once is
    a batched ``[e, 16, cap] x [e, cap, 16]`` contraction that the TPU's
    compiler makes an MXU ``convolution`` (the lanes a dilated spatial axis),
    under ``newton/hessian``; a TPU at default precision would round its
    float32 operands to bfloat16, and for a squared loss a rounded ``X'X`` is a
    rounded model, so ``ops/objective._weighted_gram`` says ``highest``. The
    candidates' margins (``newton/shrink``) are such a product too."""
    from photon_ml_tpu.algorithm.coordinates import solve_entity_bucket_traced
    from photon_ml_tpu.ops.losses import SquaredLoss

    lanes, cap, d, rows, entities = 4576, 128, 16, 3_643_392, 9_496
    newton = OptimizerConfig(OptimizerType.NEWTON, max_iterations=10,
                             rel_function_tolerance=1e-6)
    objective = GLMObjective(SquaredLoss(), l2_weight=1.0)
    with jax.enable_x64(False):
        text = jax.jit(
            lambda *a: solve_entity_bucket_traced(objective, newton, *a)
        ).lower(_shape(one_chip, (lanes, cap, d)), _shape(one_chip, (lanes, cap)),
                _shape(one_chip, (lanes, cap)), _shape(one_chip, (lanes, cap), jnp.int32),
                _shape(one_chip, (lanes,), jnp.int32), _shape(one_chip, (rows,)),
                _shape(one_chip, (entities, d))).compile().as_text()
    products = re.findall(
        r"= (f32\[[\d,]+\])[^\n]* convolution\(([^\n]*)op_name=\"([^\"]*)\"", text)
    hessians = [(shape, rest) for shape, rest, op_name in products
                if "newton/hessian/" in op_name]
    assert hessians and all(shape == f"f32[{lanes},{d},{d}]" for shape, _ in hessians)
    assert all("operand_precision={highest,highest}" in rest for _, rest in hessians)
    shrinks = [rest for shape, rest, op_name in products if "newton/shrink/" in op_name]
    assert shrinks and all("operand_precision={highest,highest}" in rest for rest in shrinks)
    # no contraction of the solve is left at the platform's default
    assert all("operand_precision={highest,highest}" in rest for _, rest, _ in products)
    for scope in ("newton/hessian/", "newton/solve/", "newton/shrink/", "newton/gradient/"):
        assert scope in text


def test_a_signature_remembered_with_its_default_layouts_lowers_the_same_program(one_chip):
    """``program_ledger._abstract`` remembers a committed array with its layout
    (PR 49), so that ``compiled_scopes`` describes the program that RAN where a
    batch was placed row-major; for every other array that layout is the
    platform's default, and on a 2x2 v5e mesh a signature that names the
    default layouts has to lower the program a signature without them does:
    a ``[65536, 16]`` block the chip keeps column-major, a vector, a scalar."""
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from photon_ml_tpu.telemetry.program_ledger import _abstract

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    mesh = Mesh(np.array(topo.devices), ("data",))
    placed = [((65536, 16), PartitionSpec("data", None)), ((65536,), PartitionSpec("data")),
              ((16,), PartitionSpec()), ((), PartitionSpec())]

    def step(x, y, w, k):
        margins = x @ w + y
        return (margins * margins).sum() * k, x.T @ margins

    jitted = jax.jit(step)
    by_sharding = [jax.ShapeDtypeStruct(shape, jnp.float32, sharding=NamedSharding(mesh, spec))
                   for shape, spec in placed]
    compiled = jitted.lower(*by_sharding).compile()
    formats = jax.tree_util.tree_leaves(compiled.input_formats)
    assert formats[0].layout.major_to_minor == (1, 0)  # the default is NOT row-major

    class Committed:  # what ``_abstract`` reads of a committed ``jax.Array``
        committed, weak_type = True, False

        def __init__(self, like, lies):
            self.shape, self.dtype, self.sharding, self.format = (
                like.shape, like.dtype, like.sharding, lies)

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(jax, "Array", Committed)
        remembered = [_abstract(Committed(like, lies))
                      for like, lies in zip(by_sharding, formats)]
    assert [r.format for r in remembered] == formats
    assert jitted.lower(*remembered).compile().as_text() == compiled.as_text()
