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
                                     graph_agg_pallas,
                                     resident_masked_mean_pallas)

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


def test_resident_masked_mean_compiles_at_reddit_shape(one_chip):
    """The full-graph pass's resident kernel at Reddit's shape: 3 parties'
    232,965-node sources, 64 wide, tables of 33. The packed source takes
    59.6 MB of the core's 128 MiB of VMEM (``pltpu.get_tpu_info`` for
    a v5e), which the kernel is allowed in full."""
    m, n, f, d = 3, 232_965, 33, 64

    def s(shape, dtype="float32"):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    _compile(lambda h, i, k: resident_masked_mean_pallas(
        h, i, k, interpret=False, vmem_limit_bytes=128 * 1024 * 1024),
        s((m, n, d)), s((m, n, f), "int32"), s((m, n, f)))


@pytest.mark.parametrize("placement", ["split", "one_chip"])
def test_full_forward_on_four_chips_keeps_kernel_off_the_mesh(
        topo, monkeypatch, placement):
    """The sharded backend's exact eval: parameters split by party over a
    2x2 v5e's four chips (Cora's 2,708 nodes, GCNII 64 wide). XLA's
    partitioner cannot split a Mosaic kernel, so that pass takes XLA's
    gather on every layer and compiles; the same pass on one chip of the
    four takes the resident kernel on every layer."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro import obs
    from repro.core import glasu
    from repro.kernels import ops as kops
    from repro.launch import sharding as shd
    from repro.launch.mesh import make_client_mesh

    monkeypatch.setattr(kops, "_tpu_memory",
                        lambda: (128 * 1024 * 1024, 1024 * 1024))
    monkeypatch.setattr(kops, "_interpret", lambda: False)
    m, n, f, d_in, n_layers = 4, 2708, 11, 358, 4
    mesh = make_client_mesh(m, devices=topo.devices)
    cfg = glasu.GlasuConfig(n_clients=m, n_layers=n_layers, hidden=D,
                            n_classes=7, d_in=d_in, backbone="gcnii",
                            agg_layers=(1, 3))
    params = jax.eval_shape(
        lambda: glasu.init_params(jax.random.PRNGKey(0), cfg))
    if placement == "split":
        param_sh = shd.tree_shardings(shd.client_param_specs(params, mesh),
                                      mesh)
        table_sh = NamedSharding(mesh, P())
    else:
        param_sh = jax.tree.map(lambda _: SingleDeviceSharding(
            topo.devices[0]), params)
        table_sh = SingleDeviceSharding(topo.devices[0])
    params = jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        params, param_sh)
    tables = (jax.ShapeDtypeStruct((m, n, d_in), "float32", sharding=table_sh),
              jax.ShapeDtypeStruct((m, n, f), "int32", sharding=table_sh),
              jax.ShapeDtypeStruct((m, n, f), "float32", sharding=table_sh))
    before = obs.counters()
    text = jax.jit(lambda p, x, i, k: glasu.full_forward(
        p, cfg, x, i, k, chunk=n)).lower(params, *tables).compile().as_text()
    paths = {k: obs.counters().get(k, 0) - before.get(k, 0)
             for k in ("full_agg:resident", "full_agg:xla")}
    resident = n_layers if placement == "one_chip" else 0
    assert paths == {"full_agg:resident": resident,
                     "full_agg:xla": n_layers - resident}
    assert text.count("tpu_custom_call") == resident
