"""The UniMP cell's files: its work counts on shapes worked by hand, its
roofline metric on a made-up record, its plain reference against a tiny
CPU step of the program, and whole CPU runs of its kind, whose check
fails when the step is broken underneath or the reference runs in its
place at a lower precision."""
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import benchcell_attn
from bench import calibrate_attn, data_attn, graphs, harness, work_attn
from bench.data_attn import Layer
from bench.models import unimp as ref

LIMITS = Path(__file__).resolve().parents[2] / "bench" / "limits"


def test_attention_counts_flops_and_compulsory_bytes():
    # 3 nodes, 5 edges, 2 heads of width 4: 2*5*4 operations per head for
    # the SDDMM and again for the SpMM; rowptr 4 and colind 5 words; q, k,
    # v read and the output written, 3 x 2 x 4 words each
    fwd = work_attn.attention_fwd(3, 5, 2, 4)
    assert fwd.flops == 2 * (2 * 5 * 4) * 2
    assert fwd.bytes == (4 + 5) * 4 + 4 * 24 * 4
    # backward: four products; q, k, v and the output's gradient read,
    # dq, dk and dv written
    bwd = work_attn.attention_bwd(3, 5, 2, 4)
    assert bwd.flops == 4 * (2 * 5 * 4) * 2
    assert bwd.bytes == (4 + 5) * 4 + 7 * 24 * 4


def test_the_cells_layers_and_their_attentions():
    cfg = json.loads((LIMITS.parent / "configs" / "products-unimp.json").read_text())
    layers = data_attn.layers(cfg)
    assert layers == [Layer(100, 4, 32, 128), Layer(128, 4, 32, 128), Layer(128, 4, 47, 47)]
    works = work_attn.attentions(10, 30, layers)
    assert [w.flops for w in works] == [f * 30 * 4 * d for d in (32, 32, 47) for f in (4, 8)]


def test_unimp_step_flops_by_hand():
    # n = 10, nnz = 30; layer 0: 5 in, 2 heads of 3, 6 out; layer 1: 6 in,
    # 2 heads of 4, averaged to 4 out
    # layer 0 dense: q, k, v 3*2*10*5*6, skip 2*10*5*6, gate 2*10*18*1
    #   = 2,760, three times (forward, weight and input gradients);
    #   attention 12*30*2*3
    # layer 1 dense: 3*2*10*6*8 + 2*10*6*4 + 2*10*12*1 = 3,600, three
    #   times; attention 12*30*2*4
    layers = [Layer(5, 2, 3, 6), Layer(6, 2, 4, 4)]
    assert work_attn.unimp_step_flops(10, 30, layers) == 3 * 2760 + 2160 + 3 * 3600 + 2880


def _roofline():
    return harness.load_module(LIMITS.parent / "metrics" / "attention_roofline.py")


@pytest.mark.parametrize("rec", [{}, {"trace": {"steps": 2, "scopes": {}}},
                                 {"trace": {"steps": 2, "scopes": {"fwd.spmm": 1.0}}}])
def test_attention_roofline_reads_nothing_without_attention_scopes(rec):
    assert _roofline().read(dict(rec, work={"attention_least_s_per_step": 0.01})) is None


def test_attention_roofline_on_a_made_up_record():
    rec = {"work": {"attention_least_s_per_step": 0.01},
           "trace": {"steps": 2, "scopes": {
               "fwd.attention": 0.3, "bwd.attention_bwd_e": 0.1,
               "bwd.attention_softmax": 0.03, "bwd.attention_softmax_bwd": 0.02,
               "fwd.spmm": 0.5, "unscoped": 1.0}}}
    got = _roofline().read(rec)
    assert got == pytest.approx(0.01 * 2 / 0.45 * 100)
    assert 0 < got <= 100


@pytest.fixture(scope="module")
def case():
    cfg = dict(benchcell_attn.TINY)
    g = graphs.make_graph(cfg, 5)
    x, y, fed, w0 = data_attn.inputs(5, g.n, cfg)
    return cfg, g, x, y, fed, w0


def test_reference_row_blocks_cover_every_row_once():
    rowptr = np.array([0, 0, 3, 3, 10, 11, 11, 30, 31, 31])
    assert ref.row_blocks(rowptr, 4) == (
        (0, 3, 0, 3), (3, 4, 3, 10), (4, 6, 10, 11), (6, 7, 11, 30), (7, 9, 30, 31))
    assert ref.row_blocks(rowptr, 100) == ((0, 9, 0, 31),)


@pytest.mark.parametrize("block_edges", [ref.BLOCK_EDGES, 64])
def test_reference_matches_a_program_step(case, block_edges, monkeypatch):
    """One block of rows or many: the reference's step is the program's."""
    import functools

    from repro.core import AutoSage, ScheduleCache
    from repro.models.gnn import unimp_forward
    from repro.sparse.csr import CSR
    from repro.train.gnn import _masked_xent

    cfg, g, x, y, fed, w0 = case
    csr = CSR(g.rowptr, g.colind, None, g.n, g.n)
    sage = AutoSage(cache=ScheduleCache(path=None), top_k=0)
    fwd = functools.partial(unimp_forward, heads=cfg["heads"], labels=y, label_mask=fed)
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.jit(jax.value_and_grad(
            lambda p: _masked_xent(fwd(p, csr, x, sage=sage), y, ~fed)))(w0)
    monkeypatch.setattr(ref, "BLOCK_EDGES", block_edges)
    assert len(ref.row_blocks(g.rowptr, block_edges)) == (1 if block_edges > g.nnz else 35)
    r_losses, r_grads, r_states = ref.train(w0, x, y, fed, g.rowptr, g.colind, 0.05, 1,
                                            "highest", cfg["heads"])
    assert r_losses[0] == pytest.approx(float(loss), rel=1e-6)
    for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(r_grads)):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), rtol=1e-4, atol=1e-6)
    for a, b, w in zip(jax.tree.leaves(r_states[0]), jax.tree.leaves(grads), jax.tree.leaves(w0)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(w) - 0.05 * np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


def test_reference_loss_falls(case):
    cfg, g, x, y, fed, w0 = case
    losses, _, _ = ref.train(w0, x, y, fed, g.rowptr, g.colind, 0.5, 3, "default", cfg["heads"])
    assert losses[2] < losses[1] < losses[0]


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    return benchcell_attn.build_tree(tmp_path_factory.mktemp("checkout"))


def test_run_is_correct_and_reports_its_metrics(tree):
    line = harness.execute(benchcell_attn.cpu_cell(tree))
    assert line["correct"] is True
    assert line["failed"] == 0 and line["attempted"] >= 1
    assert line["metrics"]["window_steps"]["value"] == line["attempted"]
    assert line["metrics"]["train_step_ms"]["value"] > 0
    assert list(line)[-1] == "checks"
    for c in line["checks"].values():
        assert c["value"] <= c["limit"]


def test_traced_run_reports_the_per_layer_metrics_of_the_unimp_cell(tree):
    spec = json.loads((LIMITS.parents[1] / "BENCHMARK.json").read_text())
    listed = [m["name"] for m in spec["per_layer"]
              if "products-unimp.full" in m.get("workloads", [])]
    assert {"step_mfu", "device_idle", "attention_roofline"} <= set(listed)
    assert "spmm_roofline" not in listed
    assert [m["name"] for m in benchcell_attn.cpu_cell(tree, trace=True).metrics] == listed


def test_a_program_without_unimp_gives_no_result(tree, monkeypatch):
    from repro.models import gnn

    monkeypatch.delattr(gnn, "init_unimp")
    with pytest.raises(harness.BenchError, match="cannot train UniMP"):
        harness.execute(benchcell_attn.cpu_cell(tree))


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

    orig = gnn._masked_xent

    def first_half(logits, y, mask):
        return orig(logits, y, mask & (jnp.cumsum(mask) <= jnp.sum(mask) // 2))

    monkeypatch.setattr(gnn, "_masked_xent", first_half)
    return "grad_gap"


def _reference_in_place(monkeypatch, **stand_in):
    """The configuration's reference, with ``stand_in``'s change, put in
    the compiled step's place."""
    from repro.train import gnn

    orig = gnn.train_full

    def broken(*a, **k):
        fr = orig(*a, **k)
        csr, x, y = a[1:4]
        fwd = k["forward"].keywords
        compiled = fr.compiled

        class Reference:
            def __call__(self, p):
                losses, _, states = ref.train(p, x, y, fwd["label_mask"], csr.rowptr,
                                              csr.colind, k["lr"], 1, "default",
                                              fwd["heads"], **stand_in)
                return losses[0], jax.tree.map(lambda w: jnp.asarray(w, jnp.float32), states[0])

            def __getattr__(self, name):
                return getattr(compiled, name)

        fr.compiled = Reference()
        return fr

    monkeypatch.setattr(gnn, "train_full", broken)
    return "grad_gap"


def _control(monkeypatch):
    return _reference_in_place(monkeypatch, dtype="bfloat16")


def _attn_bf16(monkeypatch):
    _reference_in_place(monkeypatch, attn_operand="bfloat16")
    return "loss_gap"  # at this size; at the cell's it fails grad_gap


@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch, _control, _attn_bf16])
def test_a_broken_step_is_not_correct(tree, monkeypatch, fault):
    caught = fault(monkeypatch)
    out = harness.execute(benchcell_attn.cpu_cell(tree, seed=11))
    assert out["correct"] is False
    c = out["checks"][caught]
    assert c["value"] > c["limit"]


@pytest.mark.parametrize("stand_in", sorted(calibrate_attn.STAND_INS))
def test_control_and_planted_fault_fail_the_cells_limits(tree, stand_in):
    limits = json.loads((LIMITS / "products-unimp.full.json").read_text())
    got = calibrate_attn.seed_readings(benchcell_attn.cpu_cell(tree), 7)[stand_in]
    assert any(got[k] > limits[k] for k in limits), got
