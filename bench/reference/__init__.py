"""Plain reference of the GLASU split GNN (paper Alg. 1, 3 and 4).

Straightforward ``jax.numpy`` with one Python loop over the parties. Its
matmuls are the configuration's (``precision`` in the configuration file):
float32 at the platform's default precision, which on a TPU is one
bfloat16 pass (operands rounded to bfloat16, products exact, float32
sums); the reference writes that rounding out and multiplies at
``highest``. It imports nothing of the program: the
parameter layout is the benchmark's (``init_params``), handed to the
program as its initial state, and the layer of each backbone lives in
``layer_<backbone>.py`` beside this file.

``dtype`` selects the precision of every array and operation; the control
of each cell runs the same code in ``bfloat16`` (the configuration states
float32).
"""
from __future__ import annotations

import importlib
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np


def layer_fn(backbone: str):
    return importlib.import_module(f"bench.reference.layer_{backbone}")


def jax_key(seed: int):
    """A PRNG key from any whole number (the low and high 32 bits)."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0x7FFFFFFF)


def init_params(seed: int, model: dict, d_in: int, n_classes: int):
    """He-normal weights and zero biases, every leaf stacked over the M
    parties, in one jitted call from the seed. The layout is the one the
    program trains and serves: ``{"inp", "layers": [..], "cls"}``."""
    m, h, n_layers = model["n_clients"], model["hidden"], model["n_layers"]
    layer = layer_fn(model["backbone"])

    def make(key):
        k = jax.random.split(key, n_layers + 2)
        params = {"inp": {"W": jax.random.normal(k[0], (m, d_in, h))
                          * np.sqrt(2.0 / d_in),
                          "b": jnp.zeros((m, h))},
                  "layers": [layer.init(k[l + 1], m, h)
                             for l in range(n_layers)],
                  "cls": {"W": jax.random.normal(k[-1], (m, h, n_classes))
                          * np.sqrt(1.0 / h),
                          "b": jnp.zeros((m, n_classes))}}
        return params

    return jax.jit(make)(jax_key(seed))


def agg_layers(model: dict) -> List[int]:
    """The paper's uniform placement of K aggregation layers."""
    n, k = model["n_layers"], model["k"]
    if k >= n:
        return list(range(n))
    step = n // k
    return sorted({n - 1 - i * step for i in range(k)})


def _exact(a, b):
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


def _bf16(x):
    return x.astype(jnp.bfloat16).astype(jnp.float32)


@jax.custom_vjp
def _one_pass(a, b):
    """A float32 matmul as a TPU runs it at its default precision: one MXU
    pass, each operand rounded to bfloat16, products exact, float32 sums.
    The backward matmuls round their operands the same way."""
    return _exact(_bf16(a), _bf16(b))


def _one_pass_fwd(a, b):
    return _one_pass(a, b), (a, b)


def _one_pass_bwd(res, g):
    a, b = res
    g = _bf16(g)
    return (_exact(g, jnp.swapaxes(_bf16(b), -1, -2)),
            _exact(jnp.swapaxes(_bf16(a), -1, -2), g))


_one_pass.defvjp(_one_pass_fwd, _one_pass_bwd)


def _mm(a, b):
    """The configuration's matmul: float32 at the platform's default
    precision (one bfloat16 pass on a TPU, exact on the CPU); bfloat16
    operands (the control) multiply as they are, into bfloat16."""
    if a.dtype == jnp.float32 and jax.default_backend() == "tpu":
        return _one_pass(a, b)
    return _exact(a, b)


def _cast(tree, dtype):
    return jax.tree.map(lambda x: x.astype(dtype)
                        if jnp.issubdtype(x.dtype, jnp.floating) else x, tree)


# ----------------------------------------------------------------- training
def joint_inference(model, params, batch, dtype=jnp.float32) -> Dict:
    """Alg. 3: every party's forward, mean aggregation at the aggregation
    layers; returns the stale buffers {l: (M, n, h)} = mean - own / M."""
    layer = layer_fn(model["backbone"])
    aggs = agg_layers(model)
    n_parties = model["n_clients"]
    hs, h0s = [], []
    for m in range(n_parties):
        pm = jax.tree.map(lambda x: x[m], params)
        h = _mm(batch["feats"][m], pm["inp"]["W"]) + pm["inp"]["b"]
        hs.append(h)
        h0s.append(h)
    stale = {}
    for l in range(model["n_layers"]):
        plus = []
        for m in range(n_parties):
            pm = jax.tree.map(lambda x: x[m], params)
            idx = batch["gather_idx"][l][m]
            mask = batch["gather_mask"][l][m].astype(dtype)
            plus.append(layer.forward(model, l, pm["layers"][l], hs[m],
                                      h0s[m], idx, mask, _mm))
            h0s[m] = h0s[m][batch["self_pos"][l][m]]
        if l in aggs:
            mean = sum(plus) / n_parties
            stale[l] = jnp.stack([mean - p / n_parties for p in plus])
            hs = [mean] * n_parties
        else:
            hs = plus
    return stale


def party_loss(model, pm, batch, stale_m, m, dtype=jnp.float32):
    layer = layer_fn(model["backbone"])
    aggs = agg_layers(model)
    n_parties = model["n_clients"]
    h = _mm(batch["feats"][m], pm["inp"]["W"]) + pm["inp"]["b"]
    h0 = h
    for l in range(model["n_layers"]):
        idx = batch["gather_idx"][l][m]
        mask = batch["gather_mask"][l][m].astype(dtype)
        h_plus = layer.forward(model, l, pm["layers"][l], h, h0, idx, mask,
                               _mm)
        h0 = h0[batch["self_pos"][l][m]]
        h = stale_m[l] + h_plus / n_parties if l in aggs else h_plus
    logits = _mm(h, pm["cls"]["W"]) + pm["cls"]["b"]
    logp = jax.nn.log_softmax(logits.astype(dtype), axis=-1)
    labels = batch["labels"]
    return -jnp.mean(logp[jnp.arange(labels.shape[0]), labels])


def make_round(model, dtype=jnp.float32):
    """One GLASU round: JointInference, then Q local Adam steps of every
    party against its stale buffers. Returns (params, state, (Q,) mean
    party losses). The model computes in ``dtype``; the parameters and
    Adam's state stay float32 (a lower-precision run keeps float32 master
    weights, as mixed-precision training does). Jitted once."""
    n_parties, q, lr = model["n_clients"], model["n_local_steps"], model["lr"]

    def loss_fn(pm, batch, stale_m, m):
        return party_loss(model, _cast(pm, dtype), batch, stale_m, m, dtype)

    def round_fn(params, state, batch):
        batch = dict(batch, feats=batch["feats"].astype(dtype))
        stale = joint_inference(model, _cast(params, dtype), batch, dtype)
        losses = []
        for _ in range(q):
            grads, ls = [], []
            for m in range(n_parties):
                pm = jax.tree.map(lambda x: x[m], params)
                sm = {l: s[m] for l, s in stale.items()}
                loss, g = jax.value_and_grad(loss_fn)(pm, batch, sm, m)
                ls.append(loss.astype(jnp.float32))
                grads.append(g)
            grads = jax.tree.map(lambda *g: jnp.stack(g), *grads)
            params, state = adam_update(lr, grads, state, params)
            losses.append(sum(ls) / n_parties)
        return params, state, jnp.stack(losses)

    return jax.jit(round_fn)


def adam_update(lr, grads, state, params, b1=0.9, b2=0.999, eps=1e-8):
    """Adam with bias correction (Kingma and Ba 2015), in float32."""
    step = state["step"] + 1
    mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, state["mu"], grads)
    nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, state["nu"],
                      grads)
    t = step.astype(jnp.float32)
    bc1, bc2 = 1 - b1 ** t, 1 - b2 ** t
    new = jax.tree.map(
        lambda p, m, v: p - lr * (m / bc1) / (jnp.sqrt(v / bc2) + eps),
        params, mu, nu)
    return new, {"step": step, "mu": mu, "nu": nu}


def train_reference(model, params, batches, dtype=jnp.float32,
                    mu_round: int = 1):
    """Follow the program's first rounds from the same initial parameters
    and the same sampled batches. Returns (losses (R, Q), Adam's first
    moment after round ``mu_round``, parameters after the last round), as
    numpy."""
    rnd = make_round(model, dtype)
    state = {"step": jnp.zeros((), jnp.int32),
             "mu": jax.tree.map(jnp.zeros_like, params),
             "nu": jax.tree.map(jnp.zeros_like, params)}
    losses, mu1 = [], None
    with jax.default_matmul_precision("highest"):
        for i, batch in enumerate(batches):
            params, state, ls = rnd(params, state, batch)
            losses.append(np.asarray(ls))
            if i == mu_round - 1:
                mu1 = jax.tree.map(lambda x: np.asarray(x, np.float32),
                                   state["mu"])
    params = jax.tree.map(lambda x: np.asarray(x, np.float32), params)
    return np.stack(losses), mu1, params


# --------------------------------------------------------------- full graph
def full_forward(model, params, feats, table_idx, table_mask, dtype=jnp.float32,
                 rows: int = 16384):
    """Exact full-graph forward (every node, every party's neighbour table,
    mean aggregation at the aggregation layers), computed layer by layer in
    blocks of ``rows`` destination nodes so it fits beside nothing else.
    ``feats``/``table_*`` are host arrays; returns (M, N, C) float32 numpy
    logits."""
    layer = layer_fn(model["backbone"])
    aggs = agg_layers(model)
    n_parties = model["n_clients"]
    n = feats.shape[1]
    params = _cast(params, dtype)

    @jax.jit
    def proj(x, w, b):
        return _mm(x.astype(dtype), w) + b

    blocks = {}

    def block_fn(l):
        if l not in blocks:
            def f(p, h, h0, idx, mask):
                return layer.forward(model, l, p, h, h0, idx,
                                     mask.astype(dtype), _mm)
            blocks[l] = jax.jit(f)
        return blocks[l]

    with jax.default_matmul_precision("highest"):
        hs = [proj(jnp.asarray(feats[m]), params["inp"]["W"][m],
                   params["inp"]["b"][m]) for m in range(n_parties)]
        h0s = list(hs)
        for l in range(model["n_layers"]):
            plus = []
            for m in range(n_parties):
                pm = jax.tree.map(lambda x: x[m], params["layers"][l])
                parts = []
                for lo in range(0, n, rows):
                    hi = min(lo + rows, n)
                    pad = rows - (hi - lo)
                    idx = np.pad(table_idx[m, lo:hi], ((0, pad), (0, 0)))
                    mask = np.pad(table_mask[m, lo:hi], ((0, pad), (0, 0)))
                    parts.append(block_fn(l)(pm, hs[m], h0s[m],
                                             jnp.asarray(idx),
                                             jnp.asarray(mask))[:hi - lo])
                plus.append(jnp.concatenate(parts))
            if l in aggs:
                mean = sum(plus) / n_parties
                hs = [mean] * n_parties
            else:
                hs = plus
        logits = [np.asarray((_mm(hs[m], params["cls"]["W"][m])
                              + params["cls"]["b"][m]).astype(jnp.float32))
                  for m in range(n_parties)]
    return np.stack(logits)
