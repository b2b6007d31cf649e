"""The reader of the program's line-search counter: the ratio where the
program keeps the counter, nothing where it does not (a parent commit)."""

import pytest

from benchmark.manifest import layer_metric_reader
from photon_ml_tpu.telemetry import registry as registry_module
from photon_ml_tpu.telemetry.registry import MetricsRegistry


@pytest.fixture
def registry(monkeypatch):
    registry = MetricsRegistry()
    monkeypatch.setattr(registry_module, "_DEFAULT", registry)
    return registry


def test_lockstep_trials_are_read_per_sweep(registry):
    registry.counter("train/sweeps").inc(12)  # a warm fit and three timed ones
    registry.counter("solver/lockstep_trials").inc(2052)
    assert layer_metric_reader("sweeps_re_lockstep_trials")({}) == 171.0


@pytest.mark.parametrize("sweeps", [None, 0, 12])
def test_a_program_without_the_counter_gives_nothing(registry, sweeps):
    """The parent counts its sweeps and no trials; a process that has not
    trained yet counts neither. Nothing is returned and nothing raises."""
    if sweeps is not None:
        registry.counter("train/sweeps").inc(sweeps)
    assert layer_metric_reader("sweeps_re_lockstep_trials")({}) is None
