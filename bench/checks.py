"""The numbers that decide ``correct``, and their limits.

Each reading is a gap between what the timed path produced and the plain
reference; each limit sits in ``limits/<cell>.json`` beside the readings
it was set from (see ``PERF.md``).
"""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np

LIMITS = Path(__file__).resolve().parent / "limits"
# a leaf whose reference gradient is under this share of the median leaf's
# moves by round-off alone under Adam, so its change is not compared
QUIET_LEAF = 1e-3


def _leaves(tree):
    import jax
    return [np.asarray(x, np.float64) for x in jax.tree.leaves(tree)]


def _worst_norm_gap(got, want, keep=None) -> float:
    """Worst leaf of |norm(got) - norm(want)| over the larger of the
    leaf's and the median leaf's reference norm."""
    g = np.array([np.linalg.norm(x) for x in got])
    w = np.array([np.linalg.norm(x) for x in want])
    keep = np.ones(len(w), bool) if keep is None else keep
    scale = np.maximum(w, np.median(w[keep]))
    return float((np.abs(g - w) / scale)[keep].max())


def train_readings(got, ref, params0) -> dict:
    """first_loss_gap: relative gap of the first local step's loss, the
    forward pass on the same weights and batch before any update;
    loss_gap: worst relative gap of a checked round's Q local-step
    losses; grad_gap: Adam's first moment after the first step, by the
    worst leaf; change_gap: the parameters' change over the checked rounds,
    by the worst leaf, leaving out leaves the reference gradient leaves
    unmoved."""
    losses, mu, params = got
    r_losses, r_mu, r_params = ref
    loss_gap = float(np.max(np.abs(np.asarray(losses, np.float64) - r_losses)
                            / np.abs(r_losses)))
    mu_norms = np.array([np.linalg.norm(x) for x in _leaves(r_mu)])
    keep = mu_norms >= QUIET_LEAF * np.median(mu_norms)
    p0 = _leaves(params0)
    change = [a - b for a, b in zip(_leaves(params), p0)]
    r_change = [a - b for a, b in zip(_leaves(r_params), p0)]
    first = abs(float(losses[0][0]) - float(r_losses[0][0])) \
        / abs(float(r_losses[0][0]))
    return {"first_loss_gap": first, "loss_gap": loss_gap,
            "grad_gap": _worst_norm_gap(_leaves(mu), _leaves(r_mu)),
            "change_gap": _worst_norm_gap(change, r_change, keep)}


def logit_readings(got, want) -> dict:
    """logit_gap: widest gap of a logit; logit_rms_gap: the root mean
    square of the gaps; both over the reference logits' root mean square."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    rms = np.sqrt(np.mean(want ** 2))
    diff = np.abs(got - want)
    return {"logit_gap": float(diff.max() / rms),
            "logit_rms_gap": float(np.sqrt(np.mean(diff ** 2)) / rms)}


def with_limits(readings: dict, cell: str) -> dict:
    """{name: (reading, limit)} for every reading the cell's file limits."""
    limits = json.loads((LIMITS / f"{cell}.json").read_text())["limits"]
    missing = set(readings) - set(limits)
    if missing:
        raise KeyError(f"no limit for {sorted(missing)} in {cell}.json")
    return {k: (v, float(limits[k])) for k, v in readings.items()}
