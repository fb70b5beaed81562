"""GCNII layer (Chen et al. 2020): initial residual and identity mapping,
over a masked-mean neighbourhood aggregation of a gather table whose
column 0 is the node itself.

    z   = (1 - alpha) * mean_j h[idx_j] + alpha * h0[idx_0]
    out = relu((1 - beta_l) * z + beta_l * z W + b),  beta_l = beta / (l + 1)
"""
import jax
import jax.numpy as jnp
import numpy as np


def init(key, m: int, h: int):
    return {"W": jax.random.normal(key, (m, h, h)) * np.sqrt(2.0 / h),
            "b": jnp.zeros((m, h))}


def forward(model, l, p, h, h0, idx, mask, mm):
    alpha = model["gcnii_alpha"]
    beta = model["gcnii_beta"] / (l + 1)
    g = h[idx]                                        # (n, F+1, d)
    s = jnp.sum(g * mask[..., None], axis=1)
    agg = s / jnp.maximum(jnp.sum(mask, axis=1, keepdims=True), 1.0)
    z = (1.0 - alpha) * agg + alpha * h0[idx[:, 0]]
    return jax.nn.relu((1.0 - beta) * z + beta * mm(z, p["W"]) + p["b"])
