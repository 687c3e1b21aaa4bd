"""A cell added as files alone is found by name and runs end to end on
the CPU, and its check fails when the timed path is broken underneath or
the reference runs in its place at a lower precision."""
import jax
import jax.numpy as jnp
import pytest

import benchcell
from bench import harness


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    return benchcell.build_tree(tmp_path_factory.mktemp("checkout"))


@pytest.fixture(scope="module")
def line(tree):
    return harness.execute(benchcell.cpu_cell(tree))


def test_resolves_the_added_files_by_name(tree):
    cell = benchcell.cpu_cell(tree)
    assert cell.config["num_nodes"] == 256
    assert cell.mix["kind"] == "full_graph_train"
    assert set(cell.limits) == {"loss_gap", "grad_gap", "update_gap"}
    assert [m["name"] for m in cell.metrics] == ["train_step_ms", "setup_s", "window_steps"]


def test_traced_run_reports_the_per_layer_metrics_of_its_cells_only(tree):
    cell = benchcell.cpu_cell(tree, trace=True)
    assert cell.metrics == []  # every per-layer metric lists the chip cells


def test_run_is_correct_and_reports_its_metrics(line):
    assert line["correct"] is True
    assert line["failed"] == 0 and line["attempted"] >= 1
    assert line["metrics"]["window_steps"]["value"] == line["attempted"]
    assert line["metrics"]["train_step_ms"]["value"] > 0
    assert line["metrics"]["setup_s"]["unit"] == "s"
    assert line["device"]["platform"] == "cpu"


def test_checks_come_last_with_their_limits(line):
    assert list(line)[-1] == "checks"
    assert {"loss_gap", "grad_gap", "update_gap", "autosage_fallback_total"} <= set(line["checks"])
    for c in line["checks"].values():
        assert c["value"] <= c["limit"]


def _state_unchanged(monkeypatch):
    from repro.train import gnn

    orig = gnn.train_full

    class Unchanged:
        """The compiled step, returning its loss and the state it was given."""

        def __init__(self, step):
            self.step = step

        def __call__(self, p):
            return self.step(p)[0], p

        def __getattr__(self, name):
            return getattr(self.step, name)

    def broken(*a, **k):
        fr = orig(*a, **k)
        fr.compiled = Unchanged(fr.compiled)
        return fr

    monkeypatch.setattr(gnn, "train_full", broken)
    return "update_gap"


def _half_batch(monkeypatch):
    from repro.train import gnn

    orig = gnn._xent
    monkeypatch.setattr(gnn, "_xent", lambda logits, y: orig(
        logits[: y.shape[0] // 2], y[: y.shape[0] // 2]))
    return "grad_gap"


def _reference_in_place(monkeypatch, **stand_in):
    """The configuration's reference, with ``stand_in``'s change, put in
    the compiled step's place."""
    from repro.train import gnn

    from bench.models import graphsage_mean as ref

    orig = gnn.train_full

    def broken(*a, **k):
        fr = orig(*a, **k)
        csr, x, y = a[1:4]
        compiled = fr.compiled

        class Reference:
            def __call__(self, p):
                losses, _, states = ref.train(p, x, y, csr.rowptr, csr.colind, k["lr"], 1,
                                              "default", **stand_in)
                return losses[0], jax.tree.map(lambda w: jnp.asarray(w, jnp.float32), states[0])

            def __getattr__(self, name):
                return getattr(compiled, name)

        fr.compiled = Reference()
        return fr

    monkeypatch.setattr(gnn, "train_full", broken)
    return "grad_gap"


def _control(monkeypatch):
    return _reference_in_place(monkeypatch, dtype="bfloat16")


def _spmm_bf16(monkeypatch):
    return _reference_in_place(monkeypatch, spmm_operand="bfloat16")


@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch, _control, _spmm_bf16])
def test_a_broken_step_is_not_correct(tree, monkeypatch, fault):
    caught = fault(monkeypatch)
    out = harness.execute(benchcell.cpu_cell(tree, seed=11))
    assert out["correct"] is False
    c = out["checks"][caught]
    assert c["value"] > c["limit"]
