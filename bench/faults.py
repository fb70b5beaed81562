"""Faults planted in the timed path, to show that ``correct`` catches them.

Used only by ``calibrate.py`` and the tests, never by a benchmark run
(``variant`` is ``program`` there). ``control`` leaves the program as it
is: the driver then puts the reference at the lower precision in the
program's place when it checks.

* ``unchanged``: a training step returns its state unchanged;
* ``half_batch``: each party's loss is the mean over half of the batch;
* ``no_exchange``: the aggregation leaves out the exchange between parties
  (each party aggregates only its own block);
* ``altered_answer``: one answer is altered where it is produced.
"""
from __future__ import annotations

import contextlib
import dataclasses

VARIANTS = ("program", "control", "unchanged", "half_batch", "no_exchange",
            "altered_answer")


def wrap_step(step, variant: str):
    """The backend's ``run_step``, or one that keeps its state unchanged."""
    if variant != "unchanged":
        return step
    import jax
    import jax.numpy as jnp

    def unchanged(params, opt_state, *args, **kw):
        keep = jax.tree.map(jnp.copy, (params, opt_state))
        out = step(params, opt_state, *args, **kw)
        return dataclasses.replace(out, params=keep[0], opt_state=keep[1])

    return unchanged


def _half_batch_loss(glasu):
    import jax
    import jax.numpy as jnp

    def client_loss(params_m, feats_m, batch, stale_m, labels, cfg, m_index,
                    global_index=None, fault_w=None, fault_denom=None):
        logits = glasu._client_trunk(cfg, params_m, feats_m, batch, m_index,
                                     stale_m, global_index=global_index,
                                     fault_w=fault_w, fault_denom=fault_denom)
        half = labels.shape[0] // 2
        logp = jax.nn.log_softmax(logits[:half], axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, labels[:half, None],
                                             axis=1)[:, 0])

    return client_loss


def _no_exchange(cfg, h_plus, key=None):
    m = h_plus.shape[0]
    return h_plus, h_plus * (1.0 - 1.0 / m)


def _altered(full_forward):
    def altered(*args, **kw):
        out = full_forward(*args, **kw)
        logits = out[0] if isinstance(out, tuple) else out
        logits = logits.at[:, 0, 0].add(10.0 * abs(logits).mean())
        return (logits,) + tuple(out[1:]) if isinstance(out, tuple) \
            else logits
    return altered


@contextlib.contextmanager
def patched(variant: str):
    """Plant ``variant`` in the program's round and forward functions for
    the duration (they are traced inside it)."""
    if variant not in VARIANTS:
        raise ValueError(f"unknown variant {variant!r}; one of {VARIANTS}")
    from repro.core import glasu
    saved = {k: getattr(glasu, k) for k in
             ("client_loss", "_aggregate", "full_forward", "serve_forward")}
    try:
        if variant == "half_batch":
            glasu.client_loss = _half_batch_loss(glasu)
        elif variant == "no_exchange":
            glasu._aggregate = _no_exchange
        elif variant == "altered_answer":
            glasu.full_forward = _altered(saved["full_forward"])
            glasu.serve_forward = _altered(saved["serve_forward"])
        yield
    finally:
        for k, v in saved.items():
            setattr(glasu, k, v)
