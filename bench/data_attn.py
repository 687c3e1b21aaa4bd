"""Inputs of a UniMP cell, from the seed: the layers' widths of a
configuration, and features, labels, the label-fed nodes and weights
made on the device.

Features and labels are those of ``bench/data.py`` for the same seed and
node count, so a seed gives the GraphSAGE and UniMP cells of one graph
the same data. Weights are in the program's layout
(``repro.models.gnn.init_unimp``): ``{"emb", "layers", "norms"}``.
"""
from __future__ import annotations

import math
from typing import Dict, List, NamedTuple

from bench import data


class Layer(NamedTuple):
    d_in: int
    heads: int
    d_head: int  # the width of one head
    d_out: int  # heads concatenated (hidden layers) or averaged (last)


def layers(cfg: Dict) -> List[Layer]:
    """Per layer: hidden layers have ``heads`` heads of hidden/heads,
    concatenated; the last has ``heads`` heads of the classes' width,
    averaged."""
    h, hidden, n_layers = cfg["heads"], cfg["hidden"], cfg["num_layers"]
    out, d_in = [], cfg["in_features"]
    for i in range(n_layers):
        if i == n_layers - 1:
            out.append(Layer(d_in, h, cfg["num_classes"], cfg["num_classes"]))
        else:
            out.append(Layer(d_in, h, hidden // h, hidden))
        d_in = hidden
    return out


def inputs(seed: int, n: int, cfg: Dict):
    """(x, y, label_mask, params) from ``seed``, on the device. The
    label-fed nodes are round(label_rate * n) nodes drawn by a random
    permutation; linear weights and biases are uniform in
    +-1/sqrt(fan_in), the label embedding standard normal, LayerNorm
    scale 1 and bias 0."""
    import jax
    import jax.numpy as jnp

    x, y, _ = data.inputs(seed, n, data.dims(cfg))
    specs = layers(cfg)
    n_fed = int(round(cfg["label_rate"] * n))

    @jax.jit
    def make(key):
        km, ke, kw = jax.random.split(key, 3)
        mask = jnp.zeros((n,), bool).at[jax.random.permutation(km, n)[:n_fed]].set(True)
        ks = iter(jax.random.split(kw, 9 * len(specs)))

        def uniform(shape, fan_in):
            lim = 1.0 / math.sqrt(fan_in)
            return jax.random.uniform(next(ks), shape, jnp.float32, -lim, lim)

        lays, norms = [], []
        for i, s in enumerate(specs):
            w = s.heads * s.d_head
            p = {}
            for c in "qkv":
                p[f"w{c}"] = uniform((s.d_in, w), s.d_in)
                p[f"b{c}"] = uniform((w,), s.d_in)
            p["w_skip"] = uniform((s.d_in, s.d_out), s.d_in)
            p["b_skip"] = uniform((s.d_out,), s.d_in)
            p["w_beta"] = uniform((3 * s.d_out, 1), 3 * s.d_out)
            lays.append(p)
            if i < len(specs) - 1:
                norms.append({"scale": jnp.ones((s.d_out,), jnp.float32),
                              "bias": jnp.zeros((s.d_out,), jnp.float32)})
        emb = jax.random.normal(ke, (cfg["num_classes"], cfg["in_features"]), jnp.float32)
        return mask, {"emb": emb, "layers": lays, "norms": norms}

    mask, params = make(jax.random.fold_in(jax.random.PRNGKey(data.key_seed(seed)), 1))
    return x, y, mask, params
