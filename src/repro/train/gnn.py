"""GNN training loops with AutoSAGE-scheduled aggregation.

One copy of each loop, shared by ``examples/train_gnn.py``,
``chip_smoke.py`` and the benchmark:

- `train_full`: the whole graph per step, one jitted ``value_and_grad``
  whose scheduled sparse ops, forward and backward (``spmm_bwd_b`` on the
  memoized transpose; the attention backward's pieces), are each decided,
  probed and prepared while the step is traced. GraphSAGE by default;
  UniMP (`init_unimp`, `unimp_forward`) through the same loop;
- `train_minibatch`: a sampled induced subgraph per step, every step's
  decides shared through one `BatchScheduler`.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.core import AutoSage, BatchScheduler, obs
from repro.models.gnn import init_gnn, sage_forward, sage_minibatch_forward
from repro.sparse.csr import CSR


def make_data(graph: CSR, n_classes: int, in_dim: int, seed: int = 0):
    """Random node features and labels with a graph-independent signal in
    feature 0, so the loss can fall within a few steps."""
    n = graph.n_rows
    rng = np.random.default_rng(seed)
    feats = rng.standard_normal((n, in_dim)).astype(np.float32)
    labels = feats[:, 0] * 3 + rng.standard_normal(n) * 0.3
    labels = np.digitize(
        labels, np.quantile(labels, np.linspace(0, 1, n_classes + 1)[1:-1])
    ).astype(np.int32)
    return jnp.asarray(feats), jnp.asarray(labels)


def _xent(logits: jax.Array, y: jax.Array) -> jax.Array:
    logp = jax.nn.log_softmax(logits)
    return -jnp.take_along_axis(logp, y[:, None], 1).mean()


def _masked_xent(logits: jax.Array, y: jax.Array, mask: jax.Array) -> jax.Array:
    """Mean cross-entropy over the nodes where ``mask`` holds."""
    logp = jnp.take_along_axis(jax.nn.log_softmax(logits), y[:, None], 1)[:, 0]
    return -jnp.sum(jnp.where(mask, logp, 0.0)) / jnp.sum(mask)


@dataclasses.dataclass
class FullRun:
    losses: List[float]
    trace_s: float  # tracing the step: features, decides, probes, prepares (obs span "lower")
    compile_s: float  # XLA compile of the traced step (obs span "compile")
    step_ms: List[float]  # each step, ending in block_until_ready
    compiled: object  # the compiled step (memory_analysis, as_text)


def train_full(
    cfg: ArchConfig,
    graph: CSR,
    x: jax.Array,
    y: jax.Array,
    n_classes: int,
    steps: int,
    sage: AutoSage,
    lr: float = 0.05,
    seed: int = 0,
    on_step: Optional[Callable[[int, float, float], None]] = None,
    init: Callable = init_gnn,
    forward: Callable = sage_forward,
    loss_mask: Optional[jax.Array] = None,
) -> FullRun:
    """Full-graph training with plain SGD: ``init(cfg, key, in_dim,
    n_classes)`` gives the parameters' layout and ``forward(params, graph,
    x, sage=...)`` the logits (GraphSAGE by default). The loss is the
    cross-entropy over every node, or over the nodes of ``loss_mask``.
    The step is lowered and compiled once, before the loop, so its cost
    is reported apart from the steady step times."""
    params = init(cfg, jax.random.PRNGKey(seed), x.shape[1], n_classes)

    def loss_fn(q):
        logits = forward(q, graph, x, sage=sage)
        return _xent(logits, y) if loss_mask is None else _masked_xent(logits, y, loss_mask)

    def step(p):
        loss, g = jax.value_and_grad(loss_fn)(p)
        return loss, jax.tree.map(lambda a, b: a - lr * b, p, g)

    t0 = time.perf_counter()
    with obs.span("lower"):
        lowered = jax.jit(step).lower(params)
    t1 = time.perf_counter()
    with obs.span("compile"):
        compiled = lowered.compile()
    t2 = time.perf_counter()
    losses, step_ms = [], []
    for i in range(steps):
        ts = time.perf_counter()
        loss, params = compiled(params)
        jax.block_until_ready((loss, params))
        step_ms.append((time.perf_counter() - ts) * 1e3)
        losses.append(float(loss))
        if on_step is not None:
            on_step(i, losses[-1], step_ms[-1])
    return FullRun(losses, t1 - t0, t2 - t1, step_ms, compiled)


@dataclasses.dataclass
class MinibatchRun:
    epoch_losses: List[float]
    step_ms: List[float]
    scheduler: BatchScheduler


def train_minibatch(
    cfg: ArchConfig,
    graph: CSR,
    x: jax.Array,
    y: jax.Array,
    n_classes: int,
    sage: AutoSage,
    batch_rows: int,
    epochs: int,
    steps_per_epoch: Optional[int] = None,
    probe_budget_ms: float = 2000.0,
    lr: float = 0.05,
    seed: int = 0,
    on_epoch: Optional[Callable[[int, float, BatchScheduler], None]] = None,
) -> MinibatchRun:
    """Sampled-subgraph training: one BatchScheduler serves the whole
    stream of per-step induced subgraphs (one probe per schedule bucket,
    provisional baseline until the budget reaches a bucket). Each step's
    wall time feeds `observe` — a coarse signal (fwd+bwd, not the
    aggregation kernel alone, so kernel-level drift is diluted by the
    step's fixed cost)."""
    params = init_gnn(cfg, jax.random.PRNGKey(0), x.shape[1], n_classes)
    rng = np.random.default_rng(1 + seed)
    if steps_per_epoch is None:
        steps_per_epoch = max(1, graph.n_rows // batch_rows)
    epoch_losses, step_ms = [], []
    with BatchScheduler(sage, probe_budget_ms=probe_budget_ms) as bs:
        for epoch in range(epochs):
            losses = []
            for _ in range(steps_per_epoch):
                rows = np.sort(
                    rng.choice(graph.n_rows, size=batch_rows, replace=False)
                )
                sub = graph.row_slice(rows)
                yb = y[jnp.asarray(rows)]

                def loss_fn(p):
                    return _xent(sage_minibatch_forward(p, sub, rows, x, sage=bs), yb)

                t0 = time.perf_counter()
                loss, g = jax.value_and_grad(loss_fn)(params)
                params = jax.tree.map(lambda p, gg: p - lr * gg, params, g)
                jax.block_until_ready((loss, params))
                step_ms.append((time.perf_counter() - t0) * 1e3)
                # the step's decides (forward spmm + its scheduled
                # backward) already bucketed this subgraph; last_bucket
                # avoids a second feature extraction per step
                bs.observe(bs.last_bucket, step_ms[-1])
                losses.append(float(loss))
            epoch_losses.append(float(np.mean(losses)))
            if on_epoch is not None:
                on_epoch(epoch, epoch_losses[-1], bs)
    return MinibatchRun(epoch_losses, step_ms, bs)
