"""Inputs of a GraphSAGE cell, from the seed: the layer widths of a
configuration, and features, labels and weights made on the device."""
from __future__ import annotations

import math
from typing import Dict, Tuple

import numpy as np


def dims(cfg: Dict) -> Tuple[int, ...]:
    """Widths from the input features through the hidden layers to the
    classes."""
    return tuple([cfg["in_features"]] + [cfg["hidden"]] * (cfg["num_layers"] - 1)
                 + [cfg["num_classes"]])


def key_seed(seed: int) -> int:
    """A 31-bit PRNG seed drawn from a run's seed of any size."""
    return int(np.random.default_rng(seed).integers(0, 2**31 - 1))


def inputs(seed: int, n: int, widths: Tuple[int, ...]):
    """Features, labels and weights from ``seed``, on the device, in one
    jitted call. Labels follow a noisy score of feature 0, cut into
    equally full classes, so the loss can fall; weights are uniform in
    +-1/sqrt(fan_in), as a framework's default dense layer, in the
    program's layout ({"w_agg": [...], "w_self": [...]}, (in, out) each)."""
    import jax
    import jax.numpy as jnp

    n_classes = widths[-1]

    @jax.jit
    def make(key):
        kx, ky, kw = jax.random.split(key, 3)
        x = jax.random.normal(kx, (n, widths[0]), jnp.float32)
        score = 3.0 * x[:, 0] + 0.3 * jax.random.normal(ky, (n,), jnp.float32)
        rank = jnp.argsort(jnp.argsort(score))
        y = (rank * n_classes // n).astype(jnp.int32)
        ks = jax.random.split(kw, 2 * (len(widths) - 1))

        def dense(k, d_in, d_out):
            lim = 1.0 / math.sqrt(d_in)
            return jax.random.uniform(k, (d_in, d_out), jnp.float32, -lim, lim)

        pairs = list(zip(widths[:-1], widths[1:]))
        params = {"w_agg": [dense(ks[2 * i], *p) for i, p in enumerate(pairs)],
                  "w_self": [dense(ks[2 * i + 1], *p) for i, p in enumerate(pairs)]}
        return x, y, params

    return make(jax.random.PRNGKey(key_seed(seed)))
