"""UniMP through the scheduler: the program's `unimp_forward` against a
plain per-head reference, multi-head scheduled attention against a
per-head loop of the oracle, heads in the cache key, and no row search
left in a compiled UniMP training step."""
import dataclasses
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api
from repro.configs.base import get_config
from repro.core import AutoSage, BatchScheduler, ScheduleCache, autodiff, registry
from repro.core.cache import parse_key
from repro.core.features import HardwareSpec, InputFeatures
from repro.core.scheduler import default_probe_args
from repro.kernels import ref
from repro.kernels import xla as kx
from repro.models.gnn import init_unimp, unimp_forward
from repro.sparse import CSR, fixed_degree, power_law
from repro.train.gnn import _masked_xent, make_data, train_full


def _from_degrees(deg, seed: int) -> CSR:
    """A square graph with the given row degrees and random columns."""
    rng = np.random.default_rng(seed)
    rowptr = np.concatenate([[0], np.cumsum(deg)]).astype(np.int32)
    colind = rng.integers(0, len(deg), int(rowptr[-1])).astype(np.int32)
    return CSR(rowptr, colind, None, len(deg), len(deg))


GRAPHS = {
    "power_law": lambda: power_law(96, 1.3, avg_deg=5, seed=4),
    # empty first and last rows, and a run of three in between
    "empty_rows": lambda: _from_degrees([0, 3, 1, 0, 0, 0, 4, 2, 0], 1),
    "single_row": lambda: _from_degrees([3], 2),
    "no_edges": lambda: _from_degrees([0, 0, 0, 0], 3),
}
HEADS_WIDTH = [(1, 8), (4, 8), (1, 47), (4, 47)]


@pytest.fixture(scope="module")
def sage():
    # module-scoped, as a training process keeps one
    return AutoSage(cache=ScheduleCache(path=None), probe_iters=1, probe_cap_ms=20)


def _plain_unimp(params, csr, x, labels, label_mask, heads):
    """UniMP from its equations, one head at a time through the oracle
    `ref.csr_attention_ref`, nothing of the scheduled path."""
    rowptr, colind = jnp.asarray(csr.rowptr), jnp.asarray(csr.colind)
    h = jnp.where(label_mask[:, None], x + params["emb"][labels], x)
    n_layers = len(params["layers"])
    for i, p in enumerate(params["layers"]):
        q, k, v = (h @ p[f"w{c}"] + p[f"b{c}"] for c in "qkv")
        d = q.shape[1] // heads
        ms = [ref.csr_attention_ref(rowptr, colind, *(a[:, c * d:(c + 1) * d] for a in (q, k, v)))
              for c in range(heads)]
        last = i == n_layers - 1
        m = sum(ms) / heads if last else jnp.concatenate(ms, axis=1)
        r = h @ p["w_skip"] + p["b_skip"]
        b = jax.nn.sigmoid(jnp.concatenate([m, r, m - r], axis=1) @ p["w_beta"])
        z = b * r + (1 - b) * m
        if last:
            return z
        mu = z.mean(axis=1, keepdims=True)
        var = ((z - mu) ** 2).mean(axis=1, keepdims=True)
        g = params["norms"][i]
        h = jax.nn.relu((z - mu) / jnp.sqrt(var + 1e-5) * g["scale"] + g["bias"])


def _case(csr, heads, d, seed=0):
    """Weights with random biases and LayerNorm parameters, features,
    labels and the label-fed nodes of a small UniMP: hidden layers of
    ``heads`` heads of width ``d``, a last layer of ``heads`` heads of
    the classes' width ``d``."""
    cfg = dataclasses.replace(get_config("gnn_sage"), n_layers=3, d_model=heads * d,
                              n_heads=heads)
    params = init_unimp(cfg, jax.random.PRNGKey(seed), 12, d)
    # biases and norms start at 0 and 1: draw them, so their use is checked
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), len(leaves))
    params = jax.tree.unflatten(tree, [
        a + 0.1 * jax.random.normal(k, a.shape) if a.ndim == 1 else a
        for a, k in zip(leaves, keys)])
    x, y = make_data(csr, d, 12, seed=seed)
    # 62.5% label-fed; node 0 never is, so every graph has a loss node
    mask = jnp.asarray(np.arange(csr.n_rows) % 8 >= 3)
    return params, x, y, mask


@pytest.mark.parametrize("heads,d", HEADS_WIDTH)
@pytest.mark.parametrize("graph", sorted(GRAPHS))
def test_unimp_matches_the_plain_reference(sage, graph, heads, d):
    """Logits, the masked loss and every gradient of the scheduled model
    against the plain one, in float32 at full matmul precision: the two
    sum the same products in other orders (segment sums against per-head
    loops), so they agree to float32 round-off of O(1) values (1e-5),
    and gradients, which add more terms, to 1e-4."""
    csr = GRAPHS[graph]()
    params, x, y, mask = _case(csr, heads, d)
    fwd = functools.partial(unimp_forward, heads=heads, labels=y, label_mask=mask)
    with jax.default_matmul_precision("highest"):
        got = jax.jit(lambda p: fwd(p, csr, x, sage=sage))(params)
        want = _plain_unimp(params, csr, x, y, mask, heads)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)
        loss = lambda p, model: _masked_xent(model(p), y, ~mask)
        l_got, g_got = jax.jit(jax.value_and_grad(
            lambda p: loss(p, lambda q: fwd(q, csr, x, sage=sage))))(params)
        l_want, g_want = jax.value_and_grad(
            lambda p: loss(p, lambda q: _plain_unimp(q, csr, x, y, mask, heads)))(params)
    assert float(l_got) == pytest.approx(float(l_want), rel=1e-5)
    for a, b in zip(jax.tree.leaves(g_got), jax.tree.leaves(g_want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5)


def _per_head(csr, q, k, v):
    rowptr, colind = jnp.asarray(csr.rowptr), jnp.asarray(csr.colind)
    return jnp.stack([ref.csr_attention_ref(rowptr, colind, q[:, h], k[:, h], v[:, h])
                      for h in range(q.shape[1])], axis=1)


def _qkv(csr, heads, d, seed=0):
    rng = np.random.default_rng(seed)
    return tuple(jnp.asarray(rng.standard_normal((csr.n_rows, heads, d)).astype(np.float32))
                 for _ in range(3))


def _multi_head_candidates(csr, heads, d):
    feat = InputFeatures.from_csr(csr, d, "attention", heads)
    return registry.candidates(feat, HardwareSpec.cpu(), include_pallas=True)


@pytest.mark.parametrize("d", [8, 47])
@pytest.mark.parametrize("graph", sorted(GRAPHS))
def test_multi_head_candidates_match_a_per_head_loop(graph, d):
    """Every attention candidate the registry admits at four heads, run on
    (n, 4, D) operands, against a per-head loop of the oracle; only the
    baselines, which have a head axis, are admitted."""
    csr = GRAPHS[graph]()
    q, k, v = _qkv(csr, 4, d)
    want = np.asarray(_per_head(csr, q, k, v))
    cands = _multi_head_candidates(csr, 4, d)
    assert cands and all(c.is_baseline for c in cands)
    for c in cands:
        got = c.build(c.prepare(csr.structural()))(q, k, v)
        np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-6,
                                   err_msg=c.full_name())


@pytest.mark.parametrize("d", [8, 47])
@pytest.mark.parametrize("graph", sorted(GRAPHS))
def test_multi_head_edge_chunks_equal_one_chunk(graph, d, monkeypatch):
    """The multi-head SDDMM and SpMM gather chunks of edges one after
    another; in chunks of 3 edges they give the one-chunk result bit for
    bit, as each row still adds its edges in order."""
    csr = GRAPHS[graph]()
    aux = kx.prepare_csr(csr.structural())
    q, k, v = _qkv(csr, 4, d, seed=4)
    e = kx.sddmm_csr(aux, q, k)
    out = kx.spmm_csr(aux, e, v)
    assert e.shape == (4, csr.nnz) and len(kx._edge_chunks(csr.nnz, 4 * d)) <= 1
    monkeypatch.setattr(kx, "EDGE_CHUNK_BYTES", 3 * 4 * 128 * -(-4 * d // 128))
    assert len(kx._edge_chunks(csr.nnz, 4 * d)) == -(-csr.nnz // 3)
    np.testing.assert_array_equal(np.asarray(kx.sddmm_csr(aux, q, k)), np.asarray(e))
    np.testing.assert_array_equal(np.asarray(kx.spmm_csr(aux, e, v)), np.asarray(out))


@pytest.mark.parametrize("graph", sorted(GRAPHS) + ["hub"])
def test_multi_head_row_softmax_by_row_scans(graph):
    """(H, nnz) values take the row softmax and its VJP by segmented scans
    (no scatter), against the oracle head by head: the maxima are exact
    and the row sums add the same terms in a tree order, so they agree to
    float32 round-off of O(1) values."""
    csr = _from_degrees([0, 3, 300, 1, 0], 6) if graph == "hub" else GRAPHS[graph]()
    aux = registry._dev(kx.prepare_csr(csr.structural()))
    rng = np.random.default_rng(6)
    val, grad = (jnp.asarray(rng.standard_normal((3, csr.nnz)).astype(np.float32)) * 4
                 for _ in range(2))
    probs = jax.jit(kx.row_softmax)(aux, val)
    want = ref.row_softmax_ref(aux["rowptr"], aux["colind"], val)
    np.testing.assert_allclose(np.asarray(probs), np.asarray(want), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(jax.jit(kx.row_softmax_bwd)(aux, want, grad)),
        np.asarray(ref.row_softmax_bwd_ref(aux["rowptr"], aux["colind"], want, grad)),
        rtol=1e-5, atol=1e-5)


def test_variants_without_a_head_axis_are_gated_out():
    csr = fixed_degree(64, 4, seed=5).dedup_edges()  # where row-ELL and fused apply
    for op in ("attention", "attention_bwd_e", "attention_bwd_q"):
        one = InputFeatures.from_csr(csr, 8, op)
        four = InputFeatures.from_csr(csr, 8, op, heads=4)
        admitted = {v.full_name() for v in registry.candidates(four, HardwareSpec.cpu(), True)}
        assert admitted == {v.full_name() for v in registry.candidates(one, HardwareSpec.cpu(), True)
                            if v.is_baseline}
        assert len(registry.candidates(one, HardwareSpec.cpu(), True)) > len(admitted)


@pytest.mark.parametrize("d", [8, 47])
@pytest.mark.parametrize("graph", sorted(GRAPHS))
def test_multi_head_api_attention_and_its_vjp(sage, graph, d):
    """Scheduled (n, 4, D) attention and its gradients against a per-head
    loop of the oracle, to float32 round-off."""
    csr = GRAPHS[graph]()
    q, k, v = _qkv(csr, 4, d, seed=1)
    g = _qkv(csr, 4, d, seed=2)[0]
    got, vjp = jax.vjp(lambda *a: api.attention(csr, *a, sage=sage), q, k, v)
    want, vjp_want = jax.vjp(lambda *a: _per_head(csr, *a), q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-6)
    for a, b in zip(vjp(g), vjp_want(g)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5)


def test_one_head_in_three_axes_is_the_two_axis_op(sage):
    csr = GRAPHS["power_law"]()
    q, k, v = _qkv(csr, 1, 8, seed=3)
    got = api.attention(csr, q, k, v, sage=sage)
    assert got.shape == q.shape
    np.testing.assert_array_equal(
        np.asarray(got[:, 0]), np.asarray(api.attention(csr, q[:, 0], k[:, 0], v[:, 0], sage=sage)))


def test_heads_are_part_of_the_cache_key():
    """(H=4, D=32) and (H=1, D=128) move the same bytes per edge and never
    share a decision, forward or backward."""
    csr = GRAPHS["power_law"]()
    sage = AutoSage(cache=ScheduleCache(path=None), top_k=0)
    for op in ("attention", "attention_bwd_v"):
        sage.decide(csr, 32, op, heads=4)
        sage.decide(csr, 128, op, heads=1)
        keys = sage.cache.keys_for_op(op)
        assert sorted((parse_key(k).heads, parse_key(k).f) for k in keys) == [(1, 128), (4, 32)]
        assert "|H=4|" in next(k for k in keys if parse_key(k).heads == 4)


@pytest.mark.parametrize("op,shapes", [
    ("attention", [(96, 4, 8), (96, 4, 8), (96, 4, 8)]),
    ("attention_bwd_e", [(96, 4, 8), (96, 4, 8)]),
    ("attention_bwd_q", [(4, "nnz"), (96, 4, 8)]),
])
def test_probe_operands_have_a_head_axis(op, shapes):
    csr = GRAPHS["power_law"]()
    got = default_probe_args(op, 8, seed=0, heads=4)(csr)
    assert [a.shape for a in got] == [
        tuple(csr.nnz if d == "nnz" else d for d in s) for s in shapes]


def test_batch_scheduler_refuses_multi_head_ops():
    csr = GRAPHS["power_law"]()
    bs = BatchScheduler(AutoSage(cache=ScheduleCache(path=None), top_k=0))
    q, k, v = _qkv(csr, 4, 8)
    with pytest.raises(NotImplementedError, match="4 heads"):
        api.attention(csr, q, k, v, sage=bs)


def _scoped_whiles(hlo: str):
    """``while`` ops of a compiled module whose name stack holds an
    ``autosage.`` scope."""
    return [ln for ln in hlo.splitlines() if re.search(r"\swhile\(", ln)
            and any("autosage." in n for n in re.findall(r'op_name="([^"]*)"', ln))]


def _unimp_step_hlo() -> str:
    csr = power_law(64, 1.3, avg_deg=4, seed=5)
    x, y = make_data(csr, 5, 12)
    mask = jnp.asarray(np.arange(csr.n_rows) % 8 < 5)
    cfg = dataclasses.replace(get_config("gnn_sage"), n_layers=3, d_model=16, n_heads=4)
    sage = AutoSage(cache=ScheduleCache(path=None), top_k=0)
    fwd = functools.partial(unimp_forward, heads=4, labels=y, label_mask=mask)
    fr = train_full(cfg, csr, x, y, 5, 0, sage, init=init_unimp, forward=fwd, loss_mask=~mask)
    return fr.compiled.as_text()


def test_no_row_search_in_a_unimp_training_step():
    hlo = _unimp_step_hlo()
    for scope in ("fwd.attention.f4", "bwd.attention_bwd_e.f4", "bwd.attention_softmax.f4",
                  "bwd.attention_softmax_bwd.f4", "bwd.attention_bwd_k.f4"):
        assert f"autosage.{scope}" in hlo, scope
    assert _scoped_whiles(hlo) == []


def test_the_scoped_while_guard_sees_a_row_search(monkeypatch):
    """The guard above is not vacuous: the oracle's softmax, which
    searches rowptr, put back in the backward, compiles to a while loop
    under the softmax's scope."""
    def searched(rows, val):
        return ref.row_softmax_ref(jnp.asarray(rows["rowptr"]),
                                   jnp.zeros(val.shape[-1], jnp.int32), val)

    monkeypatch.setattr(autodiff.kx, "row_softmax", searched)
    whiles = _scoped_whiles(_unimp_step_hlo())
    assert whiles and all("attention_softmax" in w for w in whiles)
    assert kx.row_softmax is searched  # the module the backward calls
