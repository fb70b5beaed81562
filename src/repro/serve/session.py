"""Joint-inference serving session.

``InferenceSession`` holds the trained per-client parameter stack (restored
via ``core.checkpoint.load_for_inference`` — params only, no optimizer or
error-feedback state), the per-client feature stores and neighbor tables,
and answers node-classification queries through the same split-model
forward the trainer evaluates with.

Query path, per dispatch:

1. **Cache probe** at the top aggregation layer (L-1). If every queried
   node hits, the answer is assembled straight from cached aggregates and
   one tiny classifier matmul — no receptive field, no cross-client
   exchange, zero wire bytes (the warm fast path the serve benchmark's
   >= 2x throughput gate measures).
2. Otherwise a **receptive-field plan** is built on the host (numpy):
   walking layers top-down, rows already cached at an aggregation layer
   are pruned — their neighbors are never materialized — and the
   remaining rows expand through the SAME padded neighbor tables the
   evaluation path uses (``core.train._eval_tables``), so a cold
   uncompressed answer matches ``core/glasu.py`` ``full_forward`` at the
   query rows. Plans are padded to bucketed static shapes: one jit trace
   per (bucket, engine), never per query.
3. One jitted dispatch (``serve_forward`` or its shard_map twin) runs the
   plan with cached rows injected after each aggregation; freshly
   computed aggregates are written back to the cache keyed on
   (node, layer) at the current ``params_version``.

Byte accounting prices exactly the FRESH rows at each aggregation layer —
each client uploads its (n_fresh, h) block and receives the aggregate
back, at the wire size of the session codec (``comm.compression``) — plus
the int32 fresh-row id lists. ``fed.simulation.log_query_traffic`` replays
the same bill into a ``MessageLog``; the serve benchmark audits the two
term-by-term.

Why injection after aggregation is exact: both ``mean`` and ``concat``
aggregation and every PR 5 codec decode per-row-independently, so a row's
served value does not depend on which other rows share its padded batch —
computing a pruned row's garbage and overwriting it cannot contaminate a
fresh row, and a fresh row's value is bitwise what shipping only the fresh
rows would produce.
"""
from __future__ import annotations

import threading
import time
from typing import Any, Dict, NamedTuple, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from .. import obs
from ..comm.compression import make_compressor
from ..core import checkpoint, glasu
from ..core.train import _eval_neighbor_tables, _eval_tables
from ..graph.feature_store import is_streamed
from ..graph.sampler import SampledBatch
from .cache import HotNodeCache
from .config import ServeConfig
from .metrics import ServeAnswer, ServeMetrics

_UNSET = object()


class QueryPlan(NamedTuple):
    batch: SampledBatch          # jnp arrays, bucket-static shapes
    inject: Dict[int, Any]       # agg layer -> (keep (n,), rows (M,n,h_agg))
    fresh: Dict[int, int]        # agg layer -> rows exchanged fresh
    fills: Dict[int, Any]        # agg layer -> (ids (n,), compute mask (n,))


class InferenceSession:
    """Answer node-classification queries on a trained GLASU model."""

    def __init__(self, params, config, data=None, *, serve=None,
                 compression=_UNSET, params_version: int = 0):
        if compression is not _UNSET:
            config = config.with_(compression=compression)
        if serve is None:
            serve = getattr(config, "serve", None) or ServeConfig()
        elif isinstance(serve, dict):
            serve = ServeConfig(**serve)
        self.config = config
        self.serve = serve
        if data is None:
            from ..graph.synth import make_vfl_dataset
            data = make_vfl_dataset(config.dataset,
                                    n_clients=config.n_clients,
                                    seed=config.seed)
            if config.method == "centralized":
                from ..core.train import make_centralized_dataset
                data = make_centralized_dataset(data)
        self.data = data
        self.mcfg = config.glasu_config(data)
        self.params = params
        self.params_version = int(params_version)
        self._comp = make_compressor(self.mcfg.compression)

        m = self.mcfg
        self.M, self.L, self.N = m.n_clients, m.n_layers, data.n_nodes
        self.h_agg = m.hidden * (self.M if m.agg == "concat" else 1)
        self._down_h = self.h_agg
        self._d_pad = max(c.feat_dim for c in data.clients)
        self._streamed = any(is_streamed(c.features) for c in data.clients)
        if self._streamed:
            # streamed store: neighbor tables only; level-0 features are
            # gathered per plan through the store's LRU (never all N rows)
            nbr_idx, nbr_mask = _eval_neighbor_tables(
                data, config.eval_table_cap, config.seed)
            self._feats_dev = None
            self._np_feats = None
        else:
            feats, nbr_idx, nbr_mask = _eval_tables(
                data, config.eval_table_cap, config.seed)
            self._feats_dev = feats                   # (M, N, d_pad) device
            self._np_feats = np.asarray(feats)
        self._nbr_idx = np.asarray(nbr_idx)           # (M, N, W)
        self._nbr_mask = np.asarray(nbr_mask)
        self._nbr_idx_dev = nbr_idx
        self._nbr_mask_dev = nbr_mask
        self.W = self._nbr_idx.shape[-1]
        self._identity = np.arange(self.N, dtype=np.int32)

        self.cache = HotNodeCache(serve.cache_entries, serve.max_staleness)
        self.metrics = ServeMetrics()
        self._lock = threading.Lock()
        self._sizes: Dict[int, list] = {}
        self._zero_labels: Dict[int, Any] = {}   # bucket -> device zeros

        if serve.engine == "sharded":
            from ..launch.mesh import make_client_mesh
            mesh = make_client_mesh(self.M,
                                    max_devices=config.mesh_devices)
            self._fwd = glasu.make_sharded_serve_fn(
                self.mcfg, mesh, compressor=self._comp)
        else:
            self._fwd = jax.jit(
                lambda p, b, inj: glasu.serve_forward(
                    p, b, self.mcfg, compressor=self._comp,
                    cache_inject=inj))

        def _cls(params, rows, real):
            # zero pad rows BEFORE the head so warm/cold assembly of the
            # same real rows is bitwise identical regardless of pad junk
            rows = rows * real[None, :, None]
            per = jax.vmap(lambda p, x: x @ p["W"] + p["b"])(params["cls"],
                                                             rows)
            return per, per.mean(axis=0)

        self._cls = jax.jit(_cls)

    # ------------------------------------------------------------ factory
    @classmethod
    def from_checkpoint(cls, ckpt_dir: str, step: Optional[int] = None,
                        data=None, *, serve=None, compression=_UNSET):
        """Build a session from a training checkpoint directory (params
        only; optimizer / error-feedback sidecars are never read).
        ``params_version`` starts at the restored training step."""
        r = checkpoint.load_for_inference(ckpt_dir, step=step, data=data)
        return cls(r.params, r.config, r.data, serve=serve,
                   compression=compression, params_version=r.step)

    # ------------------------------------------------------- plan builder
    def _plan_sizes(self, bucket: int) -> list:
        """Static per-level set sizes for one bucket: level L holds the
        padded queries; each level below can add at most M*(W-1) table
        neighbors per computed row, capped at N (identity set)."""
        if bucket not in self._sizes:
            sizes = [0] * (self.L + 1)
            sizes[self.L] = bucket
            grow = 1 + self.M * (self.W - 1)
            for l in range(self.L - 1, -1, -1):
                sizes[l] = min(self.N, sizes[l + 1] * grow)
            self._sizes[bucket] = sizes
        return self._sizes[bucket]

    def _bucket(self, b: int) -> int:
        for bk in self.serve.resolved_buckets():
            if bk >= b:
                return bk
        raise ValueError(f"batch of {b} exceeds largest bucket "
                         f"{self.serve.resolved_buckets()[-1]}")

    def _build_plan(self, q_ids: np.ndarray, bucket: int,
                    top_hit: np.ndarray, top_rows: np.ndarray) -> QueryPlan:
        """Receptive-field plan for one padded query batch (host numpy).

        Top-down: decide per level which rows must be computed (needed,
        real, not cache-hit), expand only those rows' neighbors into the
        level below, and keep EVERY real row's self-chain so the backbone's
        h0/self_pos bookkeeping stays node-aligned (GCNII reads h0 at the
        self position of every layer). ``top_hit``/``top_rows`` are the
        already-probed cache state at layer L-1 (probing again would
        double-count cache statistics).
        """
        M, L, N, W = self.M, self.L, self.N, self.W
        agg_layers = self.mcfg.agg_layers
        sizes = self._plan_sizes(bucket)
        b = len(q_ids)

        sets = [None] * (L + 1)
        needs = [None] * (L + 1)
        computes = [None] * L
        inject: Dict[int, Any] = {}
        fresh: Dict[int, int] = {}
        fills: Dict[int, Any] = {}

        ids = np.full(bucket, -1, dtype=np.int32)
        ids[:b] = q_ids
        sets[L] = ids
        needs[L] = ids >= 0

        for l in range(L - 1, -1, -1):
            cur, need = sets[l + 1], needs[l + 1]
            real = cur >= 0
            if l in agg_layers:
                n_out = sizes[l + 1]
                if l == L - 1:
                    hit = np.zeros(n_out, dtype=np.float32)
                    hit[:len(top_hit)] = top_hit
                    rows = np.zeros((n_out, M, self.h_agg),
                                    dtype=np.float32)
                    rows[:len(top_rows)] = top_rows
                else:
                    hit, rows = self.cache.lookup(
                        l, np.where(need & real, cur, -1),
                        self.params_version, (M, self.h_agg))
                hitb = (hit > 0) & real & need
                compute = need & real & ~hitb
                inject[l] = (hitb.astype(np.float32),
                             np.ascontiguousarray(rows.transpose(1, 0, 2)))
                fresh[l] = int(compute.sum())
                fills[l] = (cur.copy(), compute.copy())
            else:
                compute = need & real
            computes[l] = compute

            n_in = sizes[l]
            cnodes = cur[compute]
            if len(cnodes):
                nb = self._nbr_idx[:, cnodes, :]
                nbr_ids = nb[self._nbr_mask[:, cnodes, :] > 0]
                need_ids = np.unique(np.concatenate([cnodes, nbr_ids]))
            else:
                need_ids = cnodes
            if n_in == N:
                sets[l] = self._identity
                nmask = np.zeros(N, dtype=bool)
                nmask[need_ids] = True
                needs[l] = nmask
            else:
                self_ids = np.unique(cur[real])
                src_ids = np.union1d(self_ids, need_ids)
                ids_l = np.full(n_in, -1, dtype=np.int32)
                ids_l[:len(src_ids)] = src_ids
                sets[l] = ids_l
                nmask = np.zeros(n_in, dtype=bool)
                nmask[:len(src_ids)] = np.isin(src_ids, need_ids)
                needs[l] = nmask

        gi_t, gm_t, rv_t, sp_t = [], [], [], []
        lut = np.full(N, -1, dtype=np.int32)
        for l in range(L):  # glint: disable=GL004 host-side numpy plan building; jnp.asarray only stages the finished tables
            src, dst = sets[l], sets[l + 1]
            n_in, n_out = sizes[l], sizes[l + 1]
            safe_dst = np.maximum(dst, 0)
            ti = self._nbr_idx[:, safe_dst, :]           # (M, n_out, W)
            tm = self._nbr_mask[:, safe_dst, :]
            if n_in == N:
                pos, selfpos = ti, safe_dst
            else:
                srcr = src[src >= 0]
                lut[srcr] = np.arange(len(srcr), dtype=np.int32)
                pos, selfpos = lut[ti], lut[safe_dst]
                lut[srcr] = -1                           # reusable buffer
            gm = (tm * (pos >= 0)
                  * computes[l][None, :, None]).astype(np.float32)
            gi = np.maximum(pos, 0).astype(np.int32)
            # force column 0 = the row's own position: every row (cached,
            # chain-only, padding) gathers at least one valid entry, so
            # every h_plus is finite (gather_mean clamps its denominator,
            # GAT's masked softmax needs >= 1 live logit)
            sp = np.maximum(selfpos, 0).astype(np.int32)
            gi[:, :, 0] = sp[None, :]
            gm[:, :, 0] = 1.0
            gi_t.append(jnp.asarray(gi))
            gm_t.append(jnp.asarray(gm))
            rv_t.append(jnp.asarray(np.ascontiguousarray(
                np.broadcast_to((dst >= 0).astype(np.float32),
                                (M, n_out)))))
            sp_t.append(jnp.asarray(np.ascontiguousarray(
                np.broadcast_to(sp, (M, n_out)))))

        src0 = sets[0]
        if sizes[0] == N:
            if self._streamed:
                raise RuntimeError(
                    "query plan reached the identity set at level 0, which "
                    "a streamed feature store cannot materialize; lower the "
                    "serve buckets / eval_table_cap for this graph scale")
            feats = self._feats_dev          # resident; no per-query copy
        else:
            feats = jnp.asarray(self._gather_feats(src0))
        # labels are a dead input on the serve path; stage one zeros vector
        # per bucket explicitly (jnp.zeros here would upload its scalar
        # fill constant on every cold dispatch — transfer_guard trips on it)
        labels = self._zero_labels.get(bucket)
        if labels is None:
            labels = jnp.asarray(np.zeros(bucket, np.int32))
            self._zero_labels[bucket] = labels
        batch = SampledBatch(
            feats=feats, gather_idx=tuple(gi_t), gather_mask=tuple(gm_t),
            row_valid=tuple(rv_t), labels=labels, self_pos=tuple(sp_t))
        inject_dev = {l: (jnp.asarray(k), jnp.asarray(r))
                      for l, (k, r) in inject.items()}
        return QueryPlan(batch=batch, inject=inject_dev, fresh=fresh,
                         fills=fills)

    def _gather_feats(self, src0: np.ndarray) -> np.ndarray:
        """(M, n, d_pad) level-0 feature block for one plan: resident-array
        slice on small graphs, per-client store row gather when streamed
        (only the plan's rows ever leave disk)."""
        valid = (src0 >= 0).astype(np.float32)[None, :, None]
        if not self._streamed:
            return self._np_feats[:, np.maximum(src0, 0), :] * valid
        safe = np.maximum(src0, 0)
        f = np.zeros((self.M, len(src0), self._d_pad), np.float32)
        for m, c in enumerate(self.data.clients):
            rows = c.features[safe]
            f[m, :, :rows.shape[1]] = rows
        return f * valid

    # ----------------------------------------------------------- serving
    def _wire(self, n: int, d: int) -> int:
        if self._comp is None:
            return n * d * 4
        return self._comp.wire_bytes(n, d)

    def _price(self, fresh: Dict[int, int]) -> Tuple[int, int, int]:
        """(upload, broadcast, index) bytes for one query's fresh rows —
        the same per-layer bill ``fed.simulation.log_query_traffic``
        replays into a MessageLog."""
        m = self.mcfg
        up = down = idx = 0
        for l in m.agg_layers:
            n = fresh.get(l, 0)
            if n == 0:
                continue
            up += self.M * self._wire(n, m.hidden)
            down += self.M * self._wire(n, self._down_h)
            idx += self.M * n * 4
        return up, down, idx

    def answer(self, nodes) -> ServeAnswer:
        """Answer a node-classification query for ``nodes`` (any order,
        duplicates fine). Requests beyond ``max_batch`` are split into
        sequential dispatches and recombined."""
        nodes = np.asarray(nodes, dtype=np.int32).ravel()
        if nodes.size == 0:
            raise ValueError("empty query")
        if nodes.min() < 0 or nodes.max() >= self.N:
            raise ValueError(
                f"query ids must be in [0, {self.N}), got range "
                f"[{nodes.min()}, {nodes.max()}]")
        mb = self.serve.max_batch
        chunks = [nodes[i:i + mb] for i in range(0, len(nodes), mb)]
        answers = []
        with self._lock:
            for c in chunks:
                with obs.span("answer"):
                    ans = self._answer_locked(c)
                self.metrics.record(ans)
                answers.append(ans)
        if len(answers) == 1:
            return answers[0]
        return ServeAnswer(
            nodes=nodes,
            logits=np.concatenate([a.logits for a in answers]),
            per_client=np.concatenate([a.per_client for a in answers],
                                      axis=1),
            preds=np.concatenate([a.preds for a in answers]),
            fresh_rows={l: sum(a.fresh_rows.get(l, 0) for a in answers)
                        for l in self.mcfg.agg_layers},
            upload_bytes=sum(a.upload_bytes for a in answers),
            broadcast_bytes=sum(a.broadcast_bytes for a in answers),
            index_bytes=sum(a.index_bytes for a in answers),
            cache_hits=sum(a.cache_hits for a in answers),
            cache_misses=sum(a.cache_misses for a in answers),
            latency_s=sum(a.latency_s for a in answers),
            cold=any(a.cold for a in answers),
            params_version=self.params_version,
            log=answers[0].log)

    def _answer_locked(self, nodes: np.ndarray) -> ServeAnswer:
        """One dispatch, inside the caller's span ``answer``
        (``repro.obs``), with spans ``cache`` (the top-layer probe),
        ``plan`` (``_build_plan``), ``forward`` (``_fwd`` and its copy to
        the host) and ``classify`` (``_cls`` and its copy to the host)."""
        t0 = time.perf_counter()
        m = self.mcfg
        uniq, inv = np.unique(nodes, return_inverse=True)
        b = len(uniq)
        bucket = self._bucket(b)
        top = self.L - 1 if self.mcfg.agg_layers else None

        if top is not None:
            with obs.span("cache"):
                top_hit, top_rows = self.cache.lookup(
                    top, uniq, self.params_version, (self.M, self.h_agg))
        else:
            top_hit = np.zeros(b, dtype=np.float32)
            top_rows = np.zeros((b, self.M, self.h_agg), dtype=np.float32)

        if top is not None and bool(top_hit.all()):
            # warm fast path: no plan, no layer stack, zero wire bytes
            rows = np.zeros((bucket, self.M, self.h_agg), dtype=np.float32)
            rows[:b] = top_rows
            fresh = {l: 0 for l in m.agg_layers}
            cold = False
        else:
            with obs.span("plan"):
                plan = self._build_plan(uniq, bucket, top_hit, top_rows)
            with obs.span("forward"):
                h, aggs = self._fwd(self.params, plan.batch, plan.inject)
                # numpy roundtrip on purpose: the warm path assembles the
                # same f32 rows from cache, so both paths feed the
                # classifier bitwise-identical arrays
                rows = np.ascontiguousarray(
                    np.asarray(h).transpose(1, 0, 2)).astype(
                        np.float32, copy=False)
            for l, (ids_l, comp) in plan.fills.items():
                if comp.any():
                    stack = np.asarray(aggs[l])        # (M, n, h_agg)
                    self.cache.insert(
                        l, ids_l[comp], self.params_version,
                        np.ascontiguousarray(
                            stack[:, comp, :].transpose(1, 0, 2)))
            fresh = plan.fresh
            cold = True

        real = np.zeros(bucket, dtype=np.float32)
        real[:b] = 1.0
        with obs.span("classify"):
            per, ens = self._cls(self.params,
                                 jnp.asarray(np.ascontiguousarray(
                                     rows.transpose(1, 0, 2))),
                                 jnp.asarray(real))
            per = np.asarray(per)[:, :b, :][:, inv, :]
            ens = np.asarray(ens)[:b][inv]
        up, down, idx = self._price(fresh)
        # hit/miss on the answer are the top-layer probe's outcome — the
        # decision that picks warm vs cold; inner-layer hits show up in
        # self.cache stats and in the smaller fresh_rows bill
        n_hit = int((top_hit > 0).sum())
        n_miss = b - n_hit
        log = None
        if self.serve.record_log:
            from ..fed.simulation import MessageLog, log_query_traffic
            log = MessageLog()
            log_query_traffic(log, fresh, m, compressor=self._comp)
        return ServeAnswer(
            nodes=np.array(nodes), logits=ens, per_client=per,
            preds=np.argmax(ens, axis=-1).astype(np.int32),
            fresh_rows=dict(fresh), upload_bytes=up, broadcast_bytes=down,
            index_bytes=idx, cache_hits=n_hit, cache_misses=n_miss,
            latency_s=time.perf_counter() - t0, cold=cold,
            params_version=self.params_version, log=log)

    # -------------------------------------------------------- management
    def update_params(self, params, version: Optional[int] = None):
        """Swap in new parameters (e.g. from a newer checkpoint) and bump
        ``params_version``; cache entries outside the staleness bound are
        evicted immediately."""
        with self._lock:
            self.params = params
            self.params_version = (int(version) if version is not None
                                   else self.params_version + 1)
            self.cache.drop_older_than(self.params_version)

    def precompute(self, chunk: int = 4096) -> np.ndarray:
        """Warm the cache for EVERY node from one exact chunked
        ``full_forward`` sweep; returns the (M, N, C) full-graph logits.
        The collected aggregate stacks carry exactly the N real nodes
        (pad rows are sliced off before aggregation), so chunk padding
        can never enter the cache."""
        if self._streamed:
            raise RuntimeError(
                "precompute() sweeps full_forward over all N nodes with "
                "resident features; a streamed-store session warms its "
                "cache through served queries instead")
        with self._lock:
            logits, aggs = glasu.full_forward(
                self.params, self.mcfg, self._feats_dev,
                self._nbr_idx_dev, self._nbr_mask_dev, chunk=chunk,
                collect_agg=True)
            for l, stack in aggs.items():
                self.cache.insert(
                    l, self._identity, self.params_version,
                    np.ascontiguousarray(
                        np.asarray(stack).transpose(1, 0, 2)))
            return np.asarray(logits)
