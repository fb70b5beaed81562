"""Per-kernel validation: shape/dtype sweeps + hypothesis property tests,
allclose against the pure-jnp oracles in kernels/ref.py (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.kernels import ops
from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.graph_agg import (graph_agg_pallas,
                                     resident_masked_mean_pallas)
from repro.models.gnn import gather_mean


# ------------------------------------------------------------------ graph_agg
@pytest.mark.parametrize("n_src,n_dst,fanout,d,d_out", [
    (64, 32, 4, 16, 8),
    (300, 128, 4, 64, 32),
    (512, 200, 8, 128, 64),     # non-multiple of 128 dst
    (1000, 384, 3, 96, 48),
    (256, 77, 5, 96, 192),      # tiled d_out (> DOUT_BLOCK), ragged dst
    (256, 130, 5, 64, 320),     # tiled d_out, non-multiple-of-128 tiles
])
@pytest.mark.parametrize("dtype", [jnp.float32])
def test_graph_agg_matches_ref(n_src, n_dst, fanout, d, d_out, dtype):
    rng = np.random.default_rng(0)
    h = jnp.asarray(rng.normal(size=(n_src, d)), dtype)
    idx = jnp.asarray(rng.integers(0, n_src, size=(n_dst, fanout)), jnp.int32)
    mask = jnp.asarray(rng.random((n_dst, fanout)) < 0.8, jnp.float32)
    w = jnp.asarray(rng.normal(size=(d, d_out)), dtype)
    got = graph_agg_pallas(h, idx, mask, w, interpret=True)
    want = ref.graph_agg_ref(h, idx, mask, w)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


# ------------------------------------------------ resident masked mean
@pytest.mark.parametrize("m,n_src,n_dst,fanout,d", [
    (3, 301, 301, 5, 64),       # odd N: the packing's pad row; ragged block
    (3, 513, 513, 4, 64),       # one past a 512-node block
    (1, 700, 130, 6, 64),       # fewer destinations than sources
    (2, 257, 200, 3, 32),       # four nodes to a packed row
    (2, 100, 90, 4, 128),       # one node to a row
])
def test_resident_masked_mean_matches_gather_mean(m, n_src, n_dst, fanout,
                                                  d):
    """The resident kernel is ``gather_mean`` over the stacked parties up
    to summation order, and bitwise against a model of the order the
    kernel keeps (the one XLA's reduce used on a v5e): float masks,
    all-zero rows, pad entries at node 0 and neighbours in every lane
    group of the packed rows."""
    rng = np.random.default_rng(n_src)
    h = jnp.asarray(rng.normal(size=(m, n_src, d)), jnp.float32)
    idx = rng.integers(0, n_src, size=(m, n_dst, fanout)).astype(np.int32)
    # float weights whose products are exact, so that no fused multiply-add
    # can move the bitwise check below
    mask = rng.choice(np.float32([0.0, 0.25, 0.5, 1.0]), (m, n_dst, fanout))
    mask[:, ::5, 1:] = 0.0                  # pad entries at node 0
    idx[:, ::5, 1:] = 0
    mask[:, 3::11] = 0.0                    # rows with no neighbour
    pack = 128 // d
    rows = -(-n_src // pack)
    rows += (-rows) % 8                     # packed rows, whole tiles
    assert set(np.unique(idx[mask > 0] // rows)) == set(range(pack)), \
        "every lane group holds some neighbour"
    idx, mask = jnp.asarray(idx), jnp.asarray(mask)
    got = resident_masked_mean_pallas(h, idx, mask, interpret=True,
                                      vmem_limit_bytes=0)
    want = jax.vmap(gather_mean)(h, idx, mask)
    assert got.shape == want.shape == (m, n_dst, d)
    scale = float(jnp.max(jnp.abs(want)))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6,
                               atol=1e-6 * scale)
    np.testing.assert_array_equal(np.asarray(got)[:, 3::11], 0.0)
    # bitwise, in the order the kernel keeps: XLA's on a v5e, F on 8 sublanes
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(_sublane_order_mean(h, idx, mask)))


def _sublane_order_mean(h, idx, mask):
    """``gather_mean`` summed as XLA's TPU reduce sums an F axis laid on 8
    sublanes: slot s adds neighbours s, s+8, ... in turn, then the slots
    add as a halving tree (0+4, 1+5, 2+6, 3+7; +2; +1)."""
    g = h[jnp.arange(h.shape[0])[:, None, None], idx] * mask[..., None]
    slots = [g[:, :, s] for s in range(min(8, g.shape[2]))]
    for f in range(8, g.shape[2]):
        slots[f % 8] = slots[f % 8] + g[:, :, f]
    slots += [jnp.zeros_like(slots[0])] * (8 - len(slots))
    while len(slots) > 1:
        half = len(slots) // 2
        slots = [slots[i] + slots[i + half] for i in range(half)]
    return slots[0] / jnp.maximum(jnp.sum(mask, axis=-1, keepdims=True), 1.0)


# --------------------------------------------------- fused backbone kernels
@pytest.mark.parametrize("n_src,n_dst,fanout1,d", [
    (64, 32, 5, 16),
    (300, 130, 4, 64),          # non-multiple-of-128 dst
    (256, 77, 5, 160),          # tiled d_out (d > DOUT_BLOCK)
])
def test_gcnii_kernel_matches_ref(n_src, n_dst, fanout1, d):
    from repro.kernels.graph_agg import gcnii_layer_pallas
    rng = np.random.default_rng(1)
    h = jnp.asarray(rng.normal(size=(n_src, d)), jnp.float32)
    h0 = jnp.asarray(rng.normal(size=(n_src, d)), jnp.float32)
    idx = jnp.asarray(rng.integers(0, n_src, size=(n_dst, fanout1)), jnp.int32)
    mask = jnp.asarray(rng.random((n_dst, fanout1)) < 0.8, jnp.float32)
    w = jnp.asarray(rng.normal(size=(d, d)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(d,)), jnp.float32)
    got = gcnii_layer_pallas(h, h0, idx, mask, w, b, alpha=0.1, beta=0.5,
                             interpret=True)
    want = ref.gcnii_layer_ref(h, h0, idx, mask, w, b, 0.1, 0.5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("n_src,n_dst,fanout1,d,heads,dh", [
    (64, 32, 5, 16, 2, 8),
    (300, 130, 4, 64, 2, 32),   # non-multiple-of-128 dst
    (256, 77, 5, 96, 4, 16),    # 4 heads
    (200, 129, 9, 48, 1, 64),   # single head, wide fanout
])
def test_gat_kernel_matches_ref(n_src, n_dst, fanout1, d, heads, dh):
    from repro.kernels.graph_agg import gat_layer_pallas
    rng = np.random.default_rng(2)
    h = jnp.asarray(rng.normal(size=(n_src, d)), jnp.float32)
    idx = jnp.asarray(rng.integers(0, n_src, size=(n_dst, fanout1)), jnp.int32)
    mask = np.asarray(rng.random((n_dst, fanout1)) < 0.8, np.float32)
    mask[:, 0] = 1.0                                   # self loop always on
    mask = jnp.asarray(mask)
    w = jnp.asarray(rng.normal(size=(d, heads, dh)) * 0.2, jnp.float32)
    a_src = jnp.asarray(rng.normal(size=(heads, dh)) * 0.1, jnp.float32)
    a_dst = jnp.asarray(rng.normal(size=(heads, dh)) * 0.1, jnp.float32)
    b = jnp.asarray(rng.normal(size=(heads * dh,)), jnp.float32)
    got = gat_layer_pallas(h, idx, mask, w, a_src, a_dst, b, interpret=True)
    want = ref.gat_layer_ref(h, idx, mask, w, a_src, a_dst, b)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=3e-5, atol=3e-5)


def test_fused_ops_gradients_match_ref():
    """Training differentiates through the fused layers — the custom_vjp
    (Pallas forward, ref backward) must match end-to-end ref gradients."""
    rng = np.random.default_rng(3)
    h = jnp.asarray(rng.normal(size=(64, 16)), jnp.float32)
    h0 = jnp.asarray(rng.normal(size=(64, 16)), jnp.float32)
    idx = jnp.asarray(rng.integers(0, 64, size=(40, 5)), jnp.int32)
    mask = jnp.asarray(rng.random((40, 5)) < 0.8, jnp.float32)
    w = jnp.asarray(rng.normal(size=(16, 16)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(16,)), jnp.float32)

    g1 = jax.grad(lambda h, w: jnp.sum(ops.graph_agg(h, idx, mask, w) ** 2)
                  )(h, w)
    g2 = jax.grad(lambda h, w: jnp.sum(ref.graph_agg_ref(h, idx, mask, w) ** 2)
                  )(h, w)
    for a, b_ in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=2e-5, atol=2e-5)

    g1 = jax.grad(lambda h, w: jnp.sum(ops.gcnii_layer(
        h, h0, idx, mask, w, b, alpha=0.1, beta=0.5) ** 2))(h, w)
    g2 = jax.grad(lambda h, w: jnp.sum(ref.gcnii_layer_ref(
        h, h0, idx, mask, w, b, 0.1, 0.5) ** 2))(h, w)
    for a, b_ in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=2e-5, atol=2e-5)

    wg = jnp.asarray(rng.normal(size=(16, 2, 8)) * 0.2, jnp.float32)
    a_src = jnp.asarray(rng.normal(size=(2, 8)) * 0.1, jnp.float32)
    a_dst = jnp.asarray(rng.normal(size=(2, 8)) * 0.1, jnp.float32)
    bg = jnp.asarray(rng.normal(size=(16,)), jnp.float32)
    g1 = jax.grad(lambda h, w: jnp.sum(ops.gat_layer(
        h, idx, mask, w, a_src, a_dst, bg) ** 2))(h, wg)
    g2 = jax.grad(lambda h, w: jnp.sum(ref.gat_layer_ref(
        h, idx, mask, w, a_src, a_dst, bg) ** 2))(h, wg)
    for a, b_ in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=3e-5, atol=3e-5)


@pytest.mark.slow
@settings(max_examples=15, deadline=None)
@given(n_src=st.integers(8, 200), n_dst=st.integers(1, 150),
       fanout=st.integers(1, 6), d=st.sampled_from([8, 24, 64]),
       seed=st.integers(0, 2**31 - 1))
def test_graph_agg_property(n_src, n_dst, fanout, d, seed):
    """Property: all-masked rows give exactly zero; result is permutation-
    equivariant in destination rows."""
    rng = np.random.default_rng(seed)
    h = jnp.asarray(rng.normal(size=(n_src, d)), jnp.float32)
    idx = jnp.asarray(rng.integers(0, n_src, size=(n_dst, fanout)), jnp.int32)
    mask = jnp.asarray(rng.random((n_dst, fanout)) < 0.7, jnp.float32)
    w = jnp.asarray(rng.normal(size=(d, d)), jnp.float32)
    got = graph_agg_pallas(h, idx, mask, w, interpret=True)
    want = ref.graph_agg_ref(h, idx, mask, w)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=3e-5, atol=3e-5)
    # all-masked row -> zero output
    mask0 = mask.at[0].set(0.0)
    got0 = graph_agg_pallas(h, idx, mask0, w, interpret=True)
    np.testing.assert_allclose(np.asarray(got0[0]), 0.0, atol=1e-6)
    # permutation equivariance
    perm = rng.permutation(n_dst)
    got_p = graph_agg_pallas(h, idx[perm], mask[perm], w, interpret=True)
    np.testing.assert_allclose(np.asarray(got_p), np.asarray(got)[perm],
                               rtol=3e-5, atol=3e-5)


# ------------------------------------------------------------ flash attention
@pytest.mark.parametrize("b,s,t,h,kv,dh", [
    (1, 128, 128, 4, 4, 32),      # MHA, single block
    (2, 256, 256, 8, 2, 64),      # GQA 4:1, multi-block
    (1, 200, 200, 4, 1, 64),      # MQA, ragged seq (padding path)
    (2, 96, 320, 4, 2, 32),       # cross-length (t > s)
])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_matches_ref(b, s, t, h, kv, dh, causal):
    if causal and s != t:
        pytest.skip("causal requires square for this ref")
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.normal(size=(b, s, h, dh)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, t, kv, dh)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, t, kv, dh)), jnp.float32)
    got = flash_attention_pallas(q, k, v, causal=causal, interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("window", [32, 128, 511])
def test_flash_sliding_window(window):
    rng = np.random.default_rng(2)
    b, s, h, dh = 1, 512, 2, 32
    q = jnp.asarray(rng.normal(size=(b, s, h, dh)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, s, h, dh)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, s, h, dh)), jnp.float32)
    got = flash_attention_pallas(q, k, v, causal=True, window=window,
                                 interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_dtypes(dtype):
    rng = np.random.default_rng(3)
    b, s, h, dh = 1, 128, 2, 64
    q = jnp.asarray(rng.normal(size=(b, s, h, dh)), dtype)
    k = jnp.asarray(rng.normal(size=(b, s, h, dh)), dtype)
    v = jnp.asarray(rng.normal(size=(b, s, h, dh)), dtype)
    got = flash_attention_pallas(q, k, v, interpret=True)
    want = ref.flash_attention_ref(q, k, v)
    tol = 3e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)
    assert got.dtype == dtype


@pytest.mark.slow
@settings(max_examples=10, deadline=None)
@given(s=st.integers(16, 257), h=st.sampled_from([1, 2, 4]),
       g=st.sampled_from([1, 2]), dh=st.sampled_from([16, 32]),
       seed=st.integers(0, 2**31 - 1))
def test_flash_property(s, h, g, dh, seed):
    """Property: rows of the attention matrix sum to 1 -> constant-v gives
    constant output; causal first row attends only to itself."""
    if h % g:
        g = 1
    kv = h // g
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(1, s, h, dh)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, s, kv, dh)), jnp.float32)
    v = jnp.ones((1, s, kv, dh), jnp.float32) * 3.25
    got = flash_attention_pallas(q, k, v, causal=True, interpret=True)
    np.testing.assert_allclose(np.asarray(got), 3.25, rtol=1e-5, atol=1e-5)


def test_ops_wrappers_jit():
    rng = np.random.default_rng(4)
    q = jnp.asarray(rng.normal(size=(1, 64, 2, 32)), jnp.float32)
    out = ops.flash_attention(q, q, q, causal=True)
    assert out.shape == q.shape
    h = jnp.asarray(rng.normal(size=(50, 16)), jnp.float32)
    idx = jnp.asarray(rng.integers(0, 50, size=(20, 4)), jnp.int32)
    mask = jnp.ones((20, 4), jnp.float32)
    w = jnp.asarray(rng.normal(size=(16, 8)), jnp.float32)
    out = ops.graph_agg(h, idx, mask, w)
    assert out.shape == (20, 8)
