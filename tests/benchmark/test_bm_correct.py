"""How ``correct`` is decided, shown to fail: each reference agrees with the
program at tiny size, the bfloat16-features control does not, and a run
whose timed path is broken underneath comes out not correct."""

import numpy as np
import pytest

from benchmark import compare, run
from benchmark.spans import Spans
from bm_helpers import fit_and_compare, run_with_the_timed_path_broken, tiny_cell

WORKLOAD = "glmix-ml20m.sweeps"


def _fit_and_compare(seed: int, dtype: str = "float32"):
    return fit_and_compare(WORKLOAD, seed, dtype)


@pytest.mark.parametrize("seed", [21, 2147483999])
def test_reference_agrees_with_the_program(seed, capsys):
    sound = _fit_and_compare(seed)
    assert compare.judge(sound), sound
    assert capsys.readouterr().err.count("compare[") == len(sound)  # each beside its limit
    assert [n for n, _, _ in sound][:2] == [
        "loss_own_coef_rel_gap", "val_margin_own_coef_max_gap"]


def test_the_control_comes_out_not_correct():
    """The control is the program with its own bfloat16 feature path on. It
    fails the validation margins taken at the program's own coefficients,
    which do not turn on how far a solver got, by more than a hundred times
    the limit (and, at this size, the loss there and the tables too; at the
    cell's size on the chip the margins are what fails it, PERF.md 2)."""
    compared = _fit_and_compare(21, "bfloat16")
    assert not compare.judge(compared), compared
    by_name = {n: (v, lim) for n, v, lim in compared}
    value, limit = by_name["val_margin_own_coef_max_gap"]
    assert value > 100 * limit, (value, limit)
    assert by_name["loss_own_coef_rel_gap"][0] > by_name["loss_own_coef_rel_gap"][1]


def test_own_coefficient_numbers_sit_at_the_float32_floor():
    """Sound float32: the reported loss and the scoring program's margins
    agree with float64 arithmetic at the same coefficients to rounding,
    however far from the reference's own fit the tables are."""
    by_name = {n: v for n, v, _ in _fit_and_compare(23)}
    assert by_name["loss_own_coef_rel_gap"] < 2e-7
    assert by_name["val_margin_own_coef_max_gap"] < 3e-6
    assert by_name["item_coef_rel_l2"] > 1e-4  # the fit itself is not exact


def _zero_state(produced):
    return {k: (np.zeros_like(v) if isinstance(v, np.ndarray) else v)
            for k, v in produced.items()}


def _drop_a_sweep(produced):
    return {**produced, "losses": produced["losses"][:-1],
            "val_auc": produced["val_auc"][:-1]}


def _alter_one_coefficient(produced):
    fe = produced["fe"].copy()
    fe[3] += 0.01
    return {**produced, "fe": fe}


def _misreport_the_loss(produced):
    return {**produced, "losses": produced["losses"][:-1] + [
        produced["losses"][-1] * (1 + 2e-5)]}


@pytest.mark.parametrize("break_it", [
    None,
    _zero_state,  # a step that returns its state unchanged
    _drop_a_sweep,
    _alter_one_coefficient,  # an answer altered where it is produced
    _misreport_the_loss,  # inside every limit but the one at own coefficients
])
def test_a_run_with_the_timed_path_broken_is_not_correct(break_it, monkeypatch):
    found, result = run_with_the_timed_path_broken(WORKLOAD, break_it, monkeypatch, 33)
    assert result["correct"] is (break_it is None)
    assert result["attempted"] == found["traffic"]["min_episodes"]
    assert list(result) == ["correct", "attempted", "failed", "metrics", "device",
                            "compared"]  # every number beside its limit, last
    assert all(set(v) == {"value", "limit"} for v in result["compared"].values())
    assert result["correct"] is all(
        v["value"] is not None and v["value"] <= v["limit"]
        for v in result["compared"].values())
    assert all(v["value"] > 0 for v in result["metrics"].values())


def test_the_measured_path_refuses_to_run_without_a_chip(monkeypatch, capsys):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "")
    code = run.main(["--workload", WORKLOAD, "--seed", "1",
                     "--seconds", "1", "--trace", "0"])
    captured = capsys.readouterr()
    assert code == run.EXIT_NO_CHIP != 0
    assert "{" not in captured.out  # no result line
    assert "no accelerator" in captured.err


def test_the_chips_own_start_is_taken_out_of_setup(monkeypatch):
    """``setup_s`` is process start to the first timed episode LESS the
    seconds inside ``jax.devices()``: the TPU runtime's start, which grows
    with the processes a machine has run and is none of the repository's."""
    import time

    import jax

    real = jax.devices

    def slow_devices(*args):
        time.sleep(0.3)
        return real(*args)

    monkeypatch.setattr(jax, "devices", slow_devices)
    monkeypatch.setattr(run, "_chip_start_s", 0.0)
    assert run.accelerator(1) is None  # the CPU is no accelerator
    assert 0.3 <= run._chip_start_s < 0.4
    monkeypatch.setattr(run, "_chip_start_s", 1e6)
    _, result = run_with_the_timed_path_broken(WORKLOAD, None, monkeypatch, 5)
    assert result["metrics"]["setup_s"]["value"] < -1e5  # start to here, less 1e6


class _StallingCell:
    """Episodes of 10 ms, the third one stalls for 200 ms."""

    rows_per_episode = 1000

    def __init__(self):
        self.done = 0

    def episode(self):
        import time

        self.done += 1
        time.sleep(0.2 if self.done == 3 else 0.01)


def test_a_stall_inside_the_window_moves_the_rate():
    """The rate is all the window's rows over all of its wall time: an
    episode far off the others shows in it (a median would hide it)."""
    import statistics

    from benchmark.manifest import load_module

    driver = load_module(tiny_cell(WORKLOAD)["driver"])
    cell = _StallingCell()
    times, wall = run.measure(cell, Spans(), 0.0, 5)
    assert len(times) == 5 and sum(times) <= wall < sum(times) + 0.01
    rate = driver.Cell.end_to_end(cell, times, wall)["train_rows_per_s"][0]
    assert rate == pytest.approx(5 * 1000 / wall)
    assert rate < 0.5 * 1000 / statistics.median(times)


def test_the_window_runs_whole_episodes_until_the_time_is_up():
    cell = _StallingCell()
    times, wall = run.measure(cell, Spans(), 0.3, 3)
    assert len(times) > 3 and wall >= 0.3
    assert wall - times[-1] < 0.3  # the last episode began inside the time
