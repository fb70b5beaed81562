"""The benchmark's own graph generator, neighbour tables and dataset cache.

A configuration's graph belongs to the configuration: it is built from the
``data`` block of its file (including ``data_seed``) and is the same for
every ``--seed``. The generator is a vectorised copy of the stochastic block
model with class-complementary VFL feature blocks that the program's
``graph/synth.py`` uses, so a later change there does not change what a
cell measures:

* labels uniform over the classes;
* ``avg_deg * n / 2`` undirected edges, a ``homophily`` share of them
  inside one class (class picked by size), the rest uniform;
* party m holds a disjoint contiguous block of the feature columns, in
  which only the classes with ``c % M == m`` are separated; the others
  share one centroid per group;
* each party keeps an independent ``edge_keep_frac`` subsample of the
  edges;
* train / val / test from one permutation.

The built arrays are cached under ``bench/.cache/data`` keyed by a hash of
the configuration's content, so only the first run in a checkout builds.
"""
from __future__ import annotations

import hashlib
import json
import os
from pathlib import Path

import numpy as np

CACHE = Path(__file__).resolve().parent / ".cache" / "data"


def feature_blocks(dim: int, m: int):
    cuts = np.linspace(0, dim, m + 1).astype(int)
    return [(int(cuts[i]), int(cuts[i + 1])) for i in range(m)]


def _sbm_pairs(rng, labels, n_classes, avg_deg, homophily):
    n = len(labels)
    n_edges = int(n * avg_deg / 2)
    intra = int(n_edges * homophily)
    order = np.argsort(labels, kind="stable")
    sizes = np.bincount(labels, minlength=n_classes)
    starts = np.concatenate([[0], np.cumsum(sizes)[:-1]])
    cls = rng.choice(n_classes, size=intra, p=sizes / sizes.sum())
    src = order[starts[cls] + rng.integers(0, sizes[cls])]
    dst = order[starts[cls] + rng.integers(0, sizes[cls])]
    src = np.concatenate([src, rng.integers(0, n, n_edges - intra)])
    dst = np.concatenate([dst, rng.integers(0, n, n_edges - intra)])
    keep = src != dst
    return src[keep].astype(np.int64), dst[keep].astype(np.int64)


def _csr(n, src, dst):
    """Symmetrised, de-duplicated CSR (int32) from undirected pairs."""
    keys = np.unique(np.concatenate([src * n + dst, dst * n + src]))
    indptr = np.zeros(n + 1, np.int32)
    indptr[1:] = np.cumsum(np.bincount(keys // n, minlength=n))
    return indptr, (keys % n).astype(np.int32)


def _features(rng, labels, n_classes, dim, noise, blocks):
    m_clients = len(blocks)
    feats = np.empty((len(labels), dim), np.float32)
    for m, (lo, hi) in enumerate(blocks):
        pseudo = np.where(labels % m_clients == m, labels,
                          n_classes + labels // m_clients)
        cents = rng.standard_normal((int(pseudo.max()) + 1, hi - lo),
                                    dtype=np.float32)
        feats[:, lo:hi] = cents[pseudo]
        feats[:, lo:hi] += noise * rng.standard_normal(
            (len(labels), hi - lo), dtype=np.float32)
    return feats


def neighbor_table(indptr, indices, cap: int, rng):
    """(N, cap+1) table: column 0 the node itself, then up to ``cap``
    neighbours (a uniform subsample without replacement for hubs), with a
    0/1 mask. The same layout as the program's exact-eval tables."""
    n = len(indptr) - 1
    deg = np.diff(indptr).astype(np.int64)
    idx = np.zeros((n, cap + 1), np.int32)
    mask = np.zeros((n, cap + 1), np.float32)
    idx[:, 0] = np.arange(n)
    mask[:, 0] = 1.0
    take = np.minimum(deg, cap)
    rows = np.repeat(np.arange(n), take)
    col = np.arange(len(rows)) - np.repeat(np.cumsum(take) - take, take)
    # hubs: a random key per edge, keep the cap smallest keys of each row
    keys = rng.random(len(indices), dtype=np.float32)
    seg = np.repeat(np.arange(n), deg)
    order = np.lexsort((keys, seg))
    first = np.repeat(indptr[:-1].astype(np.int64), take)
    pick = order[first + col]
    idx[rows, 1 + col] = indices[pick]
    mask[rows, 1 + col] = 1.0
    return idx, mask


def _build(cfg: dict) -> dict:
    d, m_clients = cfg["data"], cfg["model"]["n_clients"]
    rng = np.random.default_rng(d["data_seed"])
    n, c = d["n_nodes"], d["n_classes"]
    labels = rng.integers(0, c, size=n).astype(np.int32)
    blocks = feature_blocks(d["feat_dim"], m_clients)
    feats = _features(rng, labels, c, d["feat_dim"], d["feat_noise"], blocks)
    perm = rng.permutation(n).astype(np.int32)
    n_tr, n_va = int(n * d["train_frac"]), int(n * d["val_frac"])
    src, dst = _sbm_pairs(rng, labels, c, d["avg_deg"], d["homophily"])
    out = {"labels": labels, "feats": feats, "train_idx": perm[:n_tr],
           "val_idx": perm[n_tr:n_tr + n_va],
           "test_idx": perm[n_tr + n_va:]}
    out["indptr"], out["indices"] = _csr(n, src, dst)
    cap = cfg["eval_table_cap"]
    trng = np.random.default_rng(d["data_seed"] + 1)
    idx, mask = [], []
    for m in range(m_clients):
        keep = rng.random(len(src)) < d["edge_keep_frac"]
        ip, ix = _csr(n, src[keep], dst[keep])
        out[f"indptr{m}"], out[f"indices{m}"] = ip, ix
        i, k = neighbor_table(ip, ix, cap, trng)
        idx.append(i)
        mask.append(k)
    out["table_idx"], out["table_mask"] = np.stack(idx), np.stack(mask)
    return out


def config_key(cfg: dict) -> str:
    text = json.dumps(cfg, sort_keys=True).encode()
    return hashlib.sha256(text).hexdigest()[:16]


def load(cfg: dict) -> dict:
    """The configuration's arrays, from the checkout cache (keyed by the
    configuration's content) or built anew."""
    path = CACHE / f"{cfg['name']}-{config_key(cfg)}.npz"
    if path.exists():
        with np.load(path) as z:
            return {k: z[k] for k in z.files}
    arrays = _build(cfg)
    CACHE.mkdir(parents=True, exist_ok=True)
    tmp = path.with_suffix(f".{os.getpid()}.tmp.npz")
    np.savez(tmp, **arrays)
    os.replace(tmp, path)
    return arrays


def vfl_dataset(cfg: dict, arrays: dict):
    """The arrays as the program's ``VFLDataset`` (party m: its own edges
    and its feature block)."""
    from repro.graph.graph import Graph, VFLDataset

    n = cfg["data"]["n_nodes"]
    split = (arrays["train_idx"], arrays["val_idx"], arrays["test_idx"])
    feats, labels = arrays["feats"], arrays["labels"]
    blocks = feature_blocks(feats.shape[1], cfg["model"]["n_clients"])
    clients = [Graph(n, arrays[f"indptr{m}"], arrays[f"indices{m}"],
                     np.ascontiguousarray(feats[:, lo:hi]), labels, *split)
               for m, (lo, hi) in enumerate(blocks)]
    full = Graph(n, arrays["indptr"], arrays["indices"], feats, labels,
                 *split)
    return VFLDataset(cfg["name"], clients, full)


def party_features(cfg: dict, arrays: dict) -> np.ndarray:
    """(M, N, d_pad) party feature blocks, zero-padded to the widest."""
    feats = arrays["feats"]
    blocks = feature_blocks(feats.shape[1], cfg["model"]["n_clients"])
    d_pad = max(hi - lo for lo, hi in blocks)
    out = np.zeros((len(blocks), feats.shape[0], d_pad), np.float32)
    for m, (lo, hi) in enumerate(blocks):
        out[m, :, :hi - lo] = feats[:, lo:hi]
    return out

