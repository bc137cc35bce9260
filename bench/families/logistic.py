"""Logistic regression, paper §4.1: rows from the seed, model via the program.

``make_data`` is a copy of ``repro.data.synthetic.logistic_data`` kept with
the benchmark, so that a change to the program cannot change the rows a
cell runs on.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def make_data(key, cfg):
    """{"x": (N, D), "t": (N,) in {-1, +1}}, f32, made on the device."""
    n, d, separation = cfg["n"], cfg["d"], cfg["separation"]
    k_x, k_t, k_dir = jax.random.split(key, 3)
    d_feat = d - 1  # the last column is the bias feature
    t = jnp.where(jax.random.bernoulli(k_t, 0.5, (n,)), 1.0, -1.0)
    t = t.astype(jnp.float32)
    # PCA-like decaying spectrum, then a class-mean shift along a random
    # direction.
    spectrum = 1.0 / jnp.sqrt(1.0 + jnp.arange(d_feat, dtype=jnp.float32))
    x = jax.random.normal(k_x, (n, d_feat), jnp.float32) * spectrum
    direction = jax.random.normal(k_dir, (d_feat,), jnp.float32)
    direction = direction / jnp.linalg.norm(direction)
    x = x + 0.5 * separation * t[:, None] * direction * spectrum
    x = jnp.concatenate([x, jnp.ones((n, 1), jnp.float32)], axis=1)
    return {"x": x, "t": t}


def build_model(data, cfg):
    from repro.core.bounds import GLMData
    from repro.models.bayes_glm import GLMModel

    rows = GLMData(x=data["x"], t=data["t"], xi=jnp.zeros_like(data["t"]))
    return GLMModel.logistic(rows, prior_scale=cfg["prior_scale"],
                             xi=cfg["xi"])
