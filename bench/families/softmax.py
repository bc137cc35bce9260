"""Softmax classification, paper §4.2: rows from the seed, model via the
program.

``make_data`` is a copy of ``repro.data.synthetic.softmax_data`` kept with
the benchmark, so that a change to the program cannot change the rows a
cell runs on.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def make_data(key, cfg):
    """{"x": (N, D) f32 binary features, "t": (N,) int32 class ids}, made
    on the device: per-class Bernoulli rates sigmoid(sharpness · normal)."""
    n, d, k = cfg["n"], cfg["d"], cfg["classes"]
    k_proto, k_t, k_x = jax.random.split(key, 3)
    t = jax.random.randint(k_t, (n,), 0, k)
    logits = cfg["sharpness"] * jax.random.normal(k_proto, (k, d), jnp.float32)
    rates = jax.nn.sigmoid(logits)
    u = jax.random.uniform(k_x, (n, d), jnp.float32)
    x = (u < rates[t]).astype(jnp.float32)
    return {"x": x, "t": t.astype(jnp.int32)}


def build_model(data, cfg):
    from repro.core.bounds import GLMData
    from repro.models.bayes_glm import GLMModel

    k = cfg["classes"]
    rows = GLMData(x=data["x"], t=data["t"],
                   xi=jnp.zeros((data["x"].shape[0], k), jnp.float32))
    return GLMModel.softmax(rows, n_classes=k,
                            prior_scale=cfg["prior_scale"])
