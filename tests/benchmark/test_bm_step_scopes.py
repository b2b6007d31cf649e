"""The partition of the fused step's device seconds by scope
(``benchmark/step_scopes.py``) and its seven readers, on recorded lists: which
category an ``op_name`` falls to, how nested events share their time, what the
partition holds an event to, and the manifest's seven entries."""

import pytest

from benchmark import manifest as M, step_scopes
from benchmark.manifest import layer_metric_reader, load_manifest
from photon_ml_tpu.telemetry import program_ledger
from photon_ml_tpu.telemetry.program_ledger import parse_instruction

STEP = "jit(_step_impl)/"
METRICS = {f"step_{c}_time_share_pct": c for c in step_scopes.CATEGORIES}


# -- which category an op_name falls to --------------------------------------


@pytest.mark.parametrize("op_name, expected", [
    # first match wins, in the order of the rules
    (STEP + "fe/solve/while/body/lbfgs/line_search/while/body/pallas_call", "fe"),
    (STEP + "fe/solve/psum", "fe"),
    (STEP + "extra_fe/side/solve/while/body/lbfgs/direction/dot_general", "fe"),
    (STEP + "re/user/solve/vmap()/while/body/lbfgs/line_search/while/body/mul",
     "lane_search"),
    (STEP + "mf/mf/row/solve/vmap()/while/body/lbfgs/line_search/reduce_sum",
     "lane_search"),
    (STEP + "re/user/gather/gather", "gather"),
    (STEP + "mf/mf/col/gather/transpose", "gather"),
    (STEP + "re/user/scatter/scatter", "score_scatter"),
    (STEP + "mf/mf/row/scatter/scatter", "score_scatter"),
    (STEP + "score/user/nd,nd->n/dot_general", "score_scatter"),
    (STEP + "score/global/dot_general", "score_scatter"),
    (STEP + "re/item/solve/vmap()/while/body/lbfgs/direction/while/body/mul",
     "lane_update"),
    (STEP + "re/item/solve/vmap()/while/body/lbfgs/history/concatenate",
     "lane_update"),
    (STEP + "re/item/solve/vmap()/while", "lane_update"),
    (STEP + "re/item/reduce_sum", "lane_update"),  # the lanes' counts
    (STEP + "mf/mf/col/solve/vmap(jvp())/mul", "lane_update"),
    (STEP + "residual/add", "residual"),
    (STEP + "loss/reduce_sum", "residual"),
    (STEP + "add", "unscoped"),
    ("x", "unscoped"),
    (None, "unscoped"),
    # a scope is matched wherever it stands: inside vmap(...), under a loop
    (STEP + "re/user/solve/vmap(lbfgs/line_search)/while/body/mul", "lane_search"),
    (STEP + "re/user/solve/while/body/vmap(lbfgs/history)/select_n", "lane_update"),
    (STEP + "vmap(re/user)/gather/gather", "gather"),
    # and never inside another word: a scoring is not a random effect, a
    # prefix is not the scope, the primitive at the end is not a scope
    (STEP + "score/user/gather", "score_scatter"),
    (STEP + "score/user/scatter-add", "score_scatter"),
    (STEP + "gather", "unscoped"),
    (STEP + "more/user/lbfgs/line_search/mul", "unscoped"),
    (STEP + "safe/solve/mul", "unscoped"),
    (STEP + "fe/solver/mul", "unscoped"),
    (STEP + "re/user/solve/lbfgs/line_searches/mul", "lane_update"),
    (STEP + "jit(loss)/mul", "unscoped"),
])
def test_an_op_name_falls_to_its_category(op_name, expected):
    assert step_scopes.category(op_name) == expected


@pytest.mark.parametrize("op_name, expected", [
    (STEP + "re/user/solve/vmap()/while/body/mul", "re/user"),
    (STEP + "mf/mf/row/gather/gather", "mf/mf/row"),
    (STEP + "score/item/nd,nd->n/dot_general", "score/item"),
    (STEP + "fe/solve/while/body/add", "fe"),
    (STEP + "fe/add", "-"),
    (STEP + "extra_fe/side/solve/add", "extra_fe/side"),
    (STEP + "residual/add", "residual"),
    (STEP + "add", "-"),
])
def test_an_op_name_names_its_coordinate(op_name, expected):
    assert step_scopes.coordinate(op_name) == expected


@pytest.mark.parametrize("op_name, expected", [
    # the innermost solver scope, wherever it stands
    (STEP + "re/user/solve/vmap()/while/body/lbfgs/direction/while/body/mul",
     "lbfgs/direction"),
    (STEP + "re/user/solve/while/body/vmap(lbfgs/history)/select_n", "lbfgs/history"),
    (STEP + "fe/solve/while/body/lbfgs/line_search/while/body/pallas_call",
     "lbfgs/line_search"),
    (STEP + "mf/mf/row/solve/vmap()/while/body/select_n", "solve"),
    (STEP + "fe/solve/psum", "solve"),
    # outside a solver: the lanes' counts, a gather, a scoring; a primitive
    # at the end is not a scope, nor a word that holds one
    (STEP + "re/item/reduce_sum", "-"),
    (STEP + "re/user/gather/gather", "-"),
    (STEP + "score/user/solve", "-"),
    (STEP + "re/user/solver/lbfgs/directions/mul", "-"),
    (None, "-"),
])
def test_an_op_name_names_its_solver_phase(op_name, expected):
    assert step_scopes.phase(op_name) == expected


# -- the partition on recorded lists ----------------------------------------------

WHILE = "%while.4 = (s32[], f32[8]{0}) while((s32[], f32[8]{0}) %tuple.2), body=%b"
LANES = STEP + "re/user/solve/vmap()/while"
RECORD = ({
    "while.4": ("(s32[],f32[8])while", LANES),
    "fusion.1": ("f32[8]fusion", LANES + "/body/lbfgs/line_search/while/body/mul"),
    "while.7": ("f32[8]while", LANES + "/body/lbfgs/direction/while"),
    "fusion.8": ("f32[8]fusion", LANES + "/body/lbfgs/history/concatenate"),
    "fusion.2": ("f32[8]fusion", STEP + "re/user/gather/gather"),
    "fusion.3": ("f32[8]fusion", STEP + "re/user/scatter/scatter"),
    "fusion.5": ("f32[16]fusion", STEP + "score/user/dot_general"),
    "custom-call.1": ("f32[16]custom-call", STEP + "fe/solve/while/body/pallas_call"),
    "fusion.6": ("f32[16]fusion", STEP + "residual/add"),
}, frozenset({"while.4"}))


def op(name, shape="f32[8]{0}", opcode="fusion"):
    return f"%{name} = {shape} {opcode}({shape} %p)"


def one_step(at=0.0):
    """The events of one step, 1,000 ns long, and its module event."""
    return [
        (op("fusion.2"), at + 0, 100),          # gather
        (WHILE, at + 100, 500),                 # the lanes' loop ...
        (op("fusion.1"), at + 150, 200),        # ... a trial inside it
        (op("while.7", opcode="while"), at + 350, 50),  # ... the recursion's loop
        (op("copy.11", opcode="copy"), at + 360, 20),   # ...... a compiler's copy in it
        (op("copy.9", opcode="copy"), at + 400, 100),   # ... and one in the lanes' body
        (op("fusion.8"), at + 500, 50),         # ... the pairs' shift
        (op("fusion.3"), at + 600, 50),         # scatter
        (op("fusion.5", "f32[16]{0}"), at + 650, 50),  # the re-score
        (op("custom-call.1", "f32[16]{0}", "custom-call"), at + 700, 100),
        (op("fusion.6", "f32[16]{0}"), at + 800, 50),
        (op("copy.10", opcode="copy"), at + 850, 50),  # top level, no metadata
        # 900 to 1,000: idle inside the step
    ], [(f"jit__step_impl({int(at)})", at, 1000)]


def trace_of(*devices, window=(0.0, 10000.0)):
    return {"devices": {k: {"ops": ops, "modules": modules}
                        for k, (ops, modules) in enumerate(devices)},
            "host": [("bench:window", window[0], window[1] - window[0])]}


def test_nested_events_share_their_time_and_a_loop_keeps_what_is_uncovered():
    part = step_scopes.partition(trace_of(one_step(1000.0)), RECORD, parse_instruction)
    ns = 1e-9
    assert part["seconds"] == pytest.approx({
        "fe": 100 * ns, "lane_search": 200 * ns, "gather": 100 * ns,
        "score_scatter": 100 * ns,
        # the loop's 500 less its trial's 200; a copy inside it has no
        # metadata and takes the scope of the loop round it, so it stays the
        # lanes'
        "lane_update": 300 * ns,
        "residual": 50 * ns, "unscoped": 50 * ns})
    assert part["unscoped"] == pytest.approx({"copy": 50 * ns})
    # within the category, by the innermost solver scope: the recursion's
    # loop with its copy, the shift, and what the lanes' loop keeps
    assert part["by_coordinate"] == pytest.approx({
        ("lane_update", "re/user", "lbfgs/direction"): 50 * ns,
        ("lane_update", "re/user", "lbfgs/history"): 50 * ns,
        ("lane_update", "re/user", "solve"): 200 * ns,
        ("lane_search", "re/user", "lbfgs/line_search"): 200 * ns,
        ("gather", "re/user", "-"): 100 * ns,
        ("score_scatter", "re/user", "-"): 50 * ns,
        ("score_scatter", "score/user", "-"): 50 * ns,
        ("fe", "fe", "solve"): 100 * ns,
        ("residual", "residual", "-"): 50 * ns})
    assert part["busy_s"] == pytest.approx(900 * ns) and part["devices"] == 1
    # the seven are the step's busy seconds, no instant twice and none left
    assert part["step_s"] == pytest.approx(900 * ns)


def test_events_that_overlap_without_nesting_go_to_the_one_that_started_last():
    ops = [(op("fusion.2"), 0, 300), (op("fusion.3"), 200, 300), (WHILE, 600, 10)]
    part = step_scopes.partition(
        trace_of((ops, [("jit__step_impl(1)", 0, 1000)])), RECORD, parse_instruction)
    assert part["seconds"]["gather"] == pytest.approx(200e-9)
    assert part["seconds"]["score_scatter"] == pytest.approx(300e-9)
    assert part["step_s"] == pytest.approx(part["busy_s"])


def test_two_device_planes_are_averaged_and_other_programs_left_out():
    ops, modules = one_step(0.0)
    more, more_modules = one_step(2000.0)
    # another program between the steps reuses an instruction's name
    metric = [(op("fusion.2", "f32[99]{0}"), 1200, 400)]
    metric_module = [("jit_jit_metric(7)", 1200, 400)]
    two = trace_of((ops + metric + more, modules + metric_module + more_modules),
                   (ops, modules))
    part = step_scopes.partition(two, RECORD, parse_instruction)
    assert part["devices"] == 2
    assert part["seconds"]["gather"] == pytest.approx((200 + 100) / 2 * 1e-9)
    assert part["busy_s"] == pytest.approx((2 * 900 + 400 + 900) / 2 * 1e-9)
    assert part["step_s"] == pytest.approx((2 * 900 + 900) / 2 * 1e-9)
    shares = {name: step_scopes.share(part, cat) for name, cat in METRICS.items()}
    # the seven shares sum to the step module's share of busy
    assert sum(shares.values()) == pytest.approx(100 * 2700 / 3100)
    assert shares["step_lane_search_time_share_pct"] == pytest.approx(
        100 * (400 + 200) / 2 / 1550)


def test_a_name_the_profiler_cut_short_is_held_to_the_prefix_it_kept():
    ops, modules = one_step()
    cut = [(WHILE[:24], s, d) if text == WHILE else (text, s, d) for text, s, d in ops]
    assert cut[1][0] == "%while.4 = (s32[], f32[8"
    whole = step_scopes.partition(trace_of((ops, modules)), RECORD, parse_instruction)
    assert step_scopes.partition(
        trace_of((cut, modules)), RECORD, parse_instruction) == whole
    other = [("%while.4 = (s32[], f32[9", s, d) if text == WHILE else (text, s, d)
             for text, s, d in ops]
    assert step_scopes.partition(
        trace_of((other, modules)), RECORD, parse_instruction) is None


@pytest.mark.parametrize("spoil", [
    # an event of a recorded name with another signature: another program's text
    lambda ops, modules: ([(op("fusion.2", "f32[9]{0}"), s, d) if "fusion.2" in t
                           else (t, s, d) for t, s, d in ops], modules),
    # an ENTRY loop with no event in one step of the window
    lambda ops, modules: (ops + [e for e in one_step(2000.0)[0] if e[0] != WHILE],
                          modules + one_step(2000.0)[1]),
    # no step in the window at all
    lambda ops, modules: (ops, [("jit__score_impl(3)", 0, 1000)]),
    # no event of any recorded instruction
    lambda ops, modules: ([(op("copy.10", opcode="copy"), 0, 10)], modules),
], ids=["signature", "entry-loop", "no-step", "no-match"])
def test_a_trace_that_is_not_the_records_program_reads_as_nothing(spoil):
    ops, modules = one_step()
    assert step_scopes.partition(
        trace_of((ops, modules)), RECORD, parse_instruction) is not None
    spoiled = trace_of(spoil(ops, modules))
    assert step_scopes.partition(spoiled, RECORD, parse_instruction) is None
    assert step_scopes.share(None, "fe") is None


def test_without_a_device_plane_or_a_record_every_reader_gives_nothing(monkeypatch):
    host_only = {"devices": {}, "host": [("bench:window", 0.0, 1000.0)]}
    assert step_scopes.partition(host_only, RECORD, parse_instruction) is None
    # no xplane file under the work directory (a CPU test run): nothing, and
    # nothing is compiled to find that out
    monkeypatch.setattr(step_scopes.program_trace, "newest_xplane", lambda: None)
    monkeypatch.setattr(program_ledger, "compiled_scopes",
                        lambda label: pytest.fail("compiled for nothing"))
    for name in METRICS:
        assert layer_metric_reader(name)({}) is None
    # a program from before the record (a parent commit) raises nothing,
    # and no trace is opened for it
    monkeypatch.delattr(program_ledger, "compiled_scopes")
    monkeypatch.setattr(step_scopes.program_trace, "newest_xplane",
                        lambda: pytest.fail("looked for a trace"))
    for name in METRICS:
        assert layer_metric_reader(name)({}) is None


# -- the manifest's seven entries ---------------------------------------------------


def test_the_manifest_passes_with_the_seven_entries_at_its_end():
    manifest = load_manifest()
    assert M.check_manifest(manifest) == []
    assert [m["name"] for m in manifest["per_layer"][-7:]] == [
        "step_fe_time_share_pct", "step_lane_search_time_share_pct",
        "step_lane_update_time_share_pct", "step_gather_time_share_pct",
        "step_score_scatter_time_share_pct", "step_residual_time_share_pct",
        "step_unscoped_time_share_pct"]
    assert set(METRICS) == {m["name"] for m in manifest["per_layer"][-7:]}


@pytest.mark.parametrize("name", sorted(METRICS))
def test_each_entry_has_its_reader_and_reads_the_four_chip_cell(name, monkeypatch):
    entry = next(m for m in load_manifest()["per_layer"] if m["name"] == name)
    assert entry == {
        "name": name, "unit": "%", "better": "lower", "source": "device_trace",
        "layer": "step", "moves": "train_rows_per_s",
        "workloads": ["glmix-ml20m-x4.sweeps"]}
    part = step_scopes.partition(trace_of(one_step()), RECORD, parse_instruction)
    monkeypatch.setattr(step_scopes, "of_this_run", lambda: part)
    assert layer_metric_reader(name)({}) == pytest.approx(
        100 * part["seconds"][METRICS[name]] / part["busy_s"])
