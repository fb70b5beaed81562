"""Compile the graph kernels for a described TPU v5e chip (no chip needed).

Interpret-mode parity (tests/test_kernels.py, tests/test_csr_kernel.py)
cannot see what the chip's compiler refuses: unaligned block shapes,
primitives Mosaic cannot lower, more VMEM than a kernel may use. Here each
kernel is lowered with ``interpret=False`` and compiled by the TPU compiler
installed with JAX, at cora-preset widths (n_src 512, n_dst 256, d 64,
fanout+self 4, 2 GAT heads) and at the per-tile VMEM bound the
``kernels/graph_agg.py`` docstring states.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and pytest-xdist workers
all import this file.
"""
import os

import jax
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.graph_agg import (ell_to_slabs, gat_layer_pallas,
                                     gcnii_layer_pallas, graph_agg_csr_pallas,
                                     graph_agg_pallas)

N_SRC, N_DST, FANOUT1, D, M, HEADS = 512, 256, 4, 64, 3, 2


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *shapes):
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _graph_agg(h, idx, mask, w):
    return graph_agg_pallas(h, idx, mask, w, interpret=False)


def _gcnii(h, h0, idx, mask, w, b):
    return gcnii_layer_pallas(h, h0, idx, mask, w, b, alpha=0.1, beta=0.5,
                              interpret=False)


def _gat(h, idx, mask, w, a_src, a_dst, b):
    return gat_layer_pallas(h, idx, mask, w, a_src, a_dst, b, interpret=False)


def _csr(h, idx, mask, w):
    idx_s, seg_s, ew_s, n_dst = ell_to_slabs(idx, mask)
    return graph_agg_csr_pallas(h, idx_s, seg_s, ew_s, w, n_dst,
                                interpret=False)


def _args(one_chip, kernel, lead=()):
    def s(shape, dtype="float32"):
        return jax.ShapeDtypeStruct(lead + shape, dtype, sharding=one_chip)

    h, idx = s((N_SRC, D)), s((N_DST, FANOUT1), "int32")
    mask, w = s((N_DST, FANOUT1)), s((D, D))
    if kernel == "gcnii":
        return h, h, idx, mask, w, s((D,))
    if kernel == "gat":
        dh = D // HEADS
        return (h, idx, mask, s((D, HEADS, dh)), s((HEADS, dh)),
                s((HEADS, dh)), s((D,)))
    return h, idx, mask, w


KERNELS = {"graph_agg": _graph_agg, "gcnii": _gcnii, "gat": _gat,
           "graph_agg_csr": _csr}


@pytest.mark.parametrize("kernel", sorted(KERNELS))
def test_kernel_compiles_for_v5e(one_chip, kernel):
    _compile(KERNELS[kernel], *_args(one_chip, kernel))


def test_graph_agg_compiles_vmapped_over_clients(one_chip):
    """The GLASU core vmaps every kernel over the M client stack."""
    _compile(jax.vmap(_graph_agg), *_args(one_chip, "graph_agg", lead=(M,)))


def test_graph_agg_vmem_bound_of_docstring_fits(one_chip):
    """n_src = size_cap = 512 source rows at d = d_out = 512 compiles:
    the largest per-tile claim the kernel docstring makes."""
    def s(shape, dtype="float32"):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    _compile(_graph_agg, s((512, 512)), s((512, FANOUT1), "int32"),
             s((512, FANOUT1)), s((512, 512)))
