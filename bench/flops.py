"""Operations and bytes the GLASU steps need, from their shapes.

Counted: the matmuls (2 per multiply-add) and the masked-mean
neighbourhood aggregation (a multiply and an add per gathered value).
Not counted: elementwise work (relu, the GCNII residual mixes, softmax,
Adam), which is small beside these and which XLA's own ``cost_analysis``
does count — so these are below what XLA reports (``tests/test_flops.py``
checks the ratio). Nothing recomputed is counted twice.

Bytes are the least a pass must move through HBM: every operand read once
and every result written once, and one row read per valid neighbour-table
entry. So a roofline share computed from them can only be too low, never
above the truth.
"""
from __future__ import annotations

from typing import Sequence

from bench.reference import agg_layers

F32 = 4


def _party_forward(sizes: Sequence[int], d_in: int, h: int, f1: int,
                   n_classes: int, head: bool = True) -> float:
    n = list(sizes)
    out = 2.0 * n[0] * d_in * h
    for l in range(len(n) - 1):
        out += 2.0 * n[l + 1] * f1 * h + 2.0 * n[l + 1] * h * h
    if head:
        out += 2.0 * n[-1] * h * n_classes
    return out


def _party_backward(sizes: Sequence[int], d_in: int, h: int, f1: int,
                    n_classes: int) -> float:
    """Gradients of every weight; no gradient of the input features."""
    n = list(sizes)
    out = 2.0 * n[0] * d_in * h                         # dW of the input
    for l in range(len(n) - 1):
        out += 2.0 * n[l + 1] * f1 * h                  # scatter of d(mean)
        out += 2.0 * 2.0 * n[l + 1] * h * h             # dW and dZ
    out += 2.0 * 2.0 * n[-1] * h * n_classes            # dW and dH of head
    return out


def train_round(cfg: dict, layer_sizes: Sequence[int], d_in: int,
                n_classes: int) -> float:
    """FLOPs of one GLASU round: every party's JointInference forward (its
    logits are not used, so no head), then Q local steps of forward and
    backward."""
    m = cfg["model"]
    f1 = cfg["sampler"]["fanout"] + 1
    fwd = _party_forward(layer_sizes, d_in, m["hidden"], f1, n_classes)
    ji = _party_forward(layer_sizes, d_in, m["hidden"], f1, n_classes,
                        head=False)
    bwd = _party_backward(layer_sizes, d_in, m["hidden"], f1, n_classes)
    return m["n_clients"] * (ji + m["n_local_steps"] * (fwd + bwd))


def infer_pass(cfg: dict, n_nodes: int, d_in: int, n_classes: int,
               valid_entries: Sequence[float], table_width: int):
    """(FLOPs, bytes) of one exact full-graph pass. ``valid_entries[m]``
    is the number of valid entries in party m's neighbour table."""
    m = cfg["model"]
    h, n_layers, parties = m["hidden"], m["n_layers"], m["n_clients"]
    n_agg = len(agg_layers(m))
    flops = 0.0
    nbytes = 0.0
    for valid in valid_entries:
        flops += 2.0 * n_nodes * d_in * h + 2.0 * n_nodes * h * n_classes
        flops += n_layers * (2.0 * valid * h + 2.0 * n_nodes * h * h)
        nbytes += F32 * (n_nodes * d_in + d_in * h + n_nodes * h)   # input
        nbytes += n_layers * (
            2 * F32 * n_nodes * table_width      # index and mask tables
            + F32 * valid * h                    # gathered rows
            + F32 * n_nodes * h                  # h0 at the node
            + F32 * n_nodes * h                  # the layer's output
            + F32 * h * h)                       # its weight
        nbytes += F32 * (n_nodes * h + h * n_classes + n_nodes * n_classes)
    # the mean over parties at each aggregation layer: M reads, one write
    nbytes += n_agg * F32 * n_nodes * h * (parties + 1)
    return flops, nbytes

