"""probe_compile_s and prepare_s read the program's counters by phase,
and report nothing from a program that does not count by phase."""
import json
from pathlib import Path

import pytest

from bench import harness
from repro.core import obs

ROOT = Path(__file__).resolve().parents[2]


def metric(name):
    return harness.load_module(ROOT / "bench" / "metrics" / f"{name}.py")


@pytest.fixture(autouse=True)
def _fresh_registry():
    obs.reset()
    yield
    obs.reset()


def test_probe_compile_s_sums_the_probe_phase():
    obs.REGISTRY.observe(obs.XLA_COMPILE_MS, 1500.0, phase="probe")
    obs.REGISTRY.observe(obs.XLA_COMPILE_MS, 250.0, phase="probe")
    obs.REGISTRY.observe(obs.XLA_COMPILE_MS, 40000.0, phase="compile")
    obs.REGISTRY.observe(obs.XLA_COMPILE_MS, 70.0, phase="other")
    assert metric("probe_compile_s").read({}) == pytest.approx(1.75)


def test_prepare_s_sums_full_graph_prepares_only():
    obs.REGISTRY.observe("autosage_prepare_ms", 900.0, op="spmm", variant="row_ell",
                         phase="prepare")
    obs.REGISTRY.observe("autosage_prepare_ms", 100.0, op="spmm", variant="gather_segsum",
                         phase="prepare")
    obs.REGISTRY.observe("autosage_prepare_ms", 5000.0, op="spmm", variant="row_ell",
                         phase="probe")
    assert metric("prepare_s").read({}) == pytest.approx(1.0)


@pytest.mark.parametrize("name", ["probe_compile_s", "prepare_s"])
def test_a_program_that_does_not_count_by_phase_reports_nothing(name):
    # as the parent of the phase counters records them: prepares by op
    # and variant alone, and no compile counter
    obs.REGISTRY.observe("autosage_prepare_ms", 900.0, op="spmm", variant="row_ell")
    assert metric(name).read({}) is None


@pytest.mark.parametrize("name", ["probe_compile_s", "prepare_s"])
def test_the_metrics_are_entries_of_the_benchmark(name):
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = next(m for m in spec["per_layer"] if m["name"] == name)
    assert entry["moves"] == "setup_s" and entry["layer"] == "scheduler"
    assert {"products-sage.full", "reddit-sage.full"} <= set(entry["workloads"])
