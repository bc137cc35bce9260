"""Robust Student-t regression, paper §4.3: rows from the seed, model via
the program.

``make_data`` is a copy of ``repro.data.synthetic.robust_data`` kept with
the benchmark, so that a change to the program cannot change the rows a
cell runs on.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def make_data(key, cfg):
    """{"x": (N, D), "t": (N,) real response}, f32, made on the device:
    a sparse linear response with Student-t noise and gross outliers."""
    n, d = cfg["n"], cfg["d"]
    k_x, k_w, k_mask, k_noise, k_out, k_osel = jax.random.split(key, 6)
    x = jax.random.normal(k_x, (n, d - 1), jnp.float32)
    x = jnp.concatenate([x, jnp.ones((n, 1), jnp.float32)], axis=1)
    theta_true = jax.random.normal(k_w, (d,), jnp.float32)
    mask = jax.random.bernoulli(k_mask, cfg["sparsity"], (d,))
    theta_true = jnp.where(mask, theta_true, 0.0)
    noise = jax.random.t(k_noise, cfg["nu"], (n,), jnp.float32)
    gross = cfg["outlier_scale"] * jax.random.normal(k_out, (n,), jnp.float32)
    is_out = jax.random.bernoulli(k_osel, cfg["outlier_frac"], (n,))
    y = x @ theta_true + jnp.where(is_out, gross, noise)
    return {"x": x, "t": y}


def build_model(data, cfg):
    from repro.core.bounds import GLMData
    from repro.models.bayes_glm import GLMModel

    rows = GLMData(x=data["x"], t=data["t"], xi=jnp.zeros_like(data["t"]))
    return GLMModel.robust(rows, nu=cfg["nu"], sigma=cfg["sigma"],
                           prior_scale=cfg["prior_scale"])
