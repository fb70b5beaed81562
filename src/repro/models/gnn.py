"""GNN backbone sub-layers operating on sampled bipartite blocks.

Each function implements one *client* sub-layer (paper §3.1):

    H_m^+[l] = sigma( A(E_m[l]) · H_m[l] · W_m[l] )

where the sampled bipartite adjacency A(E_m[l]) is represented by
(gather_idx, gather_mask): for each output node i, column 0 is the self loop
and columns 1..F are sampled neighbors; aggregation is a masked mean
(GraphSAGE-mean normalization of the properly-scaled FastGCN submatrix).

Backbones (paper §5.4): GCN [3], GCNII [7] (two skip connections), GAT [6].
All are written for a SINGLE client on a SINGLE sampled block; the GLASU core
vmaps them over the client axis.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def gather_mean(h, idx, mask):
    """Masked-mean neighborhood aggregation.

    h: (n_l, d); idx/mask: (n_{l+1}, F+1) -> (n_{l+1}, d)
    """
    g = h[idx]                                     # (n1, F+1, d)
    s = jnp.sum(g * mask[..., None], axis=1)
    denom = jnp.maximum(jnp.sum(mask, axis=1, keepdims=True), 1.0)
    return s / denom


def init_gcn_layer(key, d_in, d_out):
    k1, _ = jax.random.split(key)
    scale = jnp.sqrt(2.0 / d_in)
    return {"W": jax.random.normal(k1, (d_in, d_out)) * scale,
            "b": jnp.zeros((d_out,))}


def gcn_layer(p, h, h0, idx, mask, agg=None):
    """``agg``, when given, is ``gather_mean(h, idx, mask)`` computed by the
    caller (the full-graph pass computes it in one kernel for all nodes)."""
    if agg is None:
        agg = gather_mean(h, idx, mask)
    return jax.nn.relu(agg @ p["W"] + p["b"])


def init_gcnii_layer(key, d_in, d_out):
    assert d_in == d_out, "GCNII layers keep a constant width"
    return init_gcn_layer(key, d_in, d_out)


def gcnii_layer(p, h, h0, idx, mask, alpha: float = 0.1, beta: float = 0.5,
                agg=None):
    """GCNII: initial-residual + identity-mapping skip connections.
    ``agg`` as for ``gcn_layer``."""
    if agg is None:
        agg = gather_mean(h, idx, mask)
    z = (1.0 - alpha) * agg + alpha * h0[idx[:, 0]]  # h0 at the output node set
    return jax.nn.relu((1.0 - beta) * z + beta * (z @ p["W"]) + p["b"])


def init_gat_layer(key, d_in, d_out, n_heads: int = 2):
    assert d_out % n_heads == 0
    dh = d_out // n_heads
    k1, k2, k3 = jax.random.split(key, 3)
    scale = jnp.sqrt(2.0 / d_in)
    return {"W": jax.random.normal(k1, (d_in, n_heads, dh)) * scale,
            "a_src": jax.random.normal(k2, (n_heads, dh)) * 0.1,
            "a_dst": jax.random.normal(k3, (n_heads, dh)) * 0.1,
            "b": jnp.zeros((d_out,))}


def gat_layer(p, h, h0, idx, mask):
    """Multi-head GAT over the sampled fanout (masked softmax attention)."""
    n_heads, dh = p["a_src"].shape
    wh = jnp.einsum("nd,dhk->nhk", h, p["W"])       # (n_l, H, dh)
    wh_nb = wh[idx]                                 # (n1, F+1, H, dh)
    wh_self = wh[idx[:, 0]]                         # (n1, H, dh)
    e = (jnp.einsum("nhk,hk->nh", wh_self, p["a_src"])[:, None, :]
         + jnp.einsum("nfhk,hk->nfh", wh_nb, p["a_dst"]))
    e = jax.nn.leaky_relu(e, negative_slope=0.2)
    e = jnp.where(mask[..., None] > 0, e, -1e9)
    att = jax.nn.softmax(e, axis=1) * mask[..., None]
    out = jnp.einsum("nfh,nfhk->nhk", att, wh_nb)
    out = out.reshape(out.shape[0], n_heads * dh)
    return jax.nn.elu(out + p["b"])


# backbones whose aggregation is ``gather_mean`` and whose layer takes it
# precomputed as ``agg``
MASKED_MEAN = frozenset({"gcn", "gcnii"})

BACKBONES = {
    "gcn": (init_gcn_layer, gcn_layer),
    "gcnii": (init_gcnii_layer, gcnii_layer),
    "gat": (init_gat_layer, gat_layer),
}
