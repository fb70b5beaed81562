"""Serving traffic: open-loop joint-inference queries through the
micro-batcher.

Entry: ``MicroBatcher.submit`` → ``InferenceSession.answer`` with the
``ServeConfig`` defaults, on the benchmark's weights (from ``--seed``).
Requests are single-node queries. Arrivals are a Poisson process at
``rate_per_s``; node popularity is Zipf with exponent ``zipf_s`` over a
permutation of the nodes. The work is fixed by the traffic file: the
inter-arrival gaps are the exponential quantiles, and the popularity and
the queried nodes are drawn from ``draw_seed``. ``--seed`` only shuffles
their order (and makes the weights), so every seed gets the same set of
gaps and the same multiset of queries.

Set-up answers one cold query at every plan bucket (each compiles once),
then replays ``warmup_seconds`` of the same traffic so the hot-node cache
reaches its steady hit rate. In the window each request is timed from when
it was due; the window closes when every request due in it has been
answered (or ``drain_s`` has passed: those count as failed).

The traffic file sets ``rate_per_s``, ``zipf_s``, ``warmup_seconds`` and
``drain_s``.
"""
from __future__ import annotations

import time

import numpy as np

from bench import checks, data, faults, reference
from bench.drivers.train import experiment_config


def schedule(tr: dict, seconds: float, n_nodes: int, rng, stream: int):
    """(due offsets in seconds, node ids) of ``rate_per_s * seconds``
    requests: a fixed set of gaps and queries (``draw_seed``, ``stream``)
    in the order ``rng`` gives them."""
    rate = tr["rate_per_s"]
    n = max(1, int(round(rate * seconds)))
    draw = np.random.default_rng([int(tr["draw_seed"]), stream])
    perm = np.random.default_rng(int(tr["draw_seed"])).permutation(n_nodes)
    p = 1.0 / np.arange(1, n_nodes + 1) ** tr["zipf_s"]
    nodes = perm[draw.choice(n_nodes, size=n, p=p / p.sum())]
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / rate
    rng.shuffle(gaps)
    return np.cumsum(gaps) - gaps[0], rng.permutation(nodes).astype(np.int32)


def _drive(batcher, due, nodes, t0, spans):
    """Submit each request when it is due; returns (send times, futures,
    completion times)."""
    n = len(due)
    sent = np.zeros(n)
    done = np.full(n, np.nan)
    futures = []
    for i in range(n):
        wait = t0 + due[i] - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        with spans("generator"):
            sent[i] = time.perf_counter()
            fut = batcher.submit([nodes[i]])
        fut.add_done_callback(
            lambda f, i=i: done.__setitem__(i, time.perf_counter()))
        futures.append(fut)
    return sent, futures, done


def run(run):
    import jax
    from repro.serve import InferenceSession, ServeConfig
    from repro.serve.batcher import MicroBatcher

    cfg, tr = run.config, run.traffic
    with run.spans("data"):
        arrays = data.load(cfg)
        dataset = data.vfl_dataset(cfg, arrays)
    cap = cfg["eval_table_cap"]
    if max(np.diff(c.indptr).max() for c in dataset.clients) > cap:
        raise RuntimeError("a node has more neighbours than the exact-eval "
                           "table holds; the reference would see others")
    exp = experiment_config(cfg, run.seed, eval_every=0)
    mcfg = exp.glasu_config(dataset)
    params = reference.init_params(run.seed, cfg["model"], mcfg.d_in,
                                   mcfg.n_classes)
    rng = np.random.default_rng(run.seed)
    n_nodes = dataset.n_nodes
    dispatches = []

    with faults.patched(run.args.variant):
        session = InferenceSession(params, exp, data=dataset,
                                   serve=ServeConfig())
        answer = session.answer

        def traced_answer(nodes):
            with run.spans("answer"):
                ans = answer(nodes)
            dispatches.append((time.perf_counter(), ans.cold,
                               ans.latency_s, len(nodes)))
            return ans

        session.answer = traced_answer
        for b in session.serve.resolved_buckets():
            session.answer(rng.choice(n_nodes, size=b, replace=False))
        batcher = MicroBatcher(session)
        try:
            due, nodes = schedule(tr, tr["warmup_seconds"], n_nodes, rng, 0)
            _, futs, _ = _drive(batcher, due, nodes, time.perf_counter(),
                                run.spans)
            for f in futs:
                f.result(timeout=tr["drain_s"])
            jax.block_until_ready(session.params)
            run.end_setup()

            due, nodes = schedule(tr, run.args.seconds, n_nodes, rng, 1)
            run.begin_window()
            t0 = run.window[0]
            sent, futs, done = _drive(batcher, due, nodes, t0, run.spans)
            deadline = time.perf_counter() + tr["drain_s"]
            for f in futs:
                try:
                    f.result(timeout=max(0.0, deadline - time.perf_counter()))
                except Exception:  # noqa: BLE001 - counted as failed below
                    pass
            run.end_window()
        finally:
            batcher.close()

    ok = np.array([f.done() and f.exception() is None for f in futs])
    latency = (done - (t0 + due))[ok]
    in_window = [d for d in dispatches if d[0] >= t0]
    run.facts.update(
        requests=len(due),
        dispatches=len(in_window),
        cold_dispatches=[d[2] for d in in_window if d[1]],
        gen_lag_s=sent - (t0 + due))
    answered = np.flatnonzero(ok)
    logits = np.stack([futs[i].result().logits[0] for i in answered]) \
        if len(answered) else np.zeros((0, mcfg.n_classes))
    rec = {"nodes": nodes[answered], "logits": logits,
           "params": jax.tree.map(np.asarray, params),
           "feats": data.party_features(cfg, arrays)}
    # every neighbour fits the table (checked above), so the subsample's
    # draw never matters and the reference's table is the graph itself
    tables = [data.neighbor_table(arrays[f"indptr{m}"],
                                  arrays[f"indices{m}"], cap,
                                  np.random.default_rng(0))
              for m in range(cfg["model"]["n_clients"])]
    rec["idx"] = np.stack([t[0] for t in tables])
    rec["mask"] = np.stack([t[1] for t in tables])
    del session, params
    return {"e2e": {"serve_p99_ms": float(np.percentile(latency, 99)) * 1e3,
                    "serve_p50_ms": float(np.percentile(latency, 50)) * 1e3},
            "attempted": len(due), "failed": int((~ok).sum()), "rec": rec}


def check(run, result):
    """Every answer of the window (cold and warm), its ensemble logits
    against the reference's exact full-graph forward at the queried node."""
    import jax.numpy as jnp

    rec, model = result["rec"], run.config["model"]
    ref = reference.full_forward(model, rec["params"], rec["feats"],
                                 rec["idx"], rec["mask"]).mean(axis=0)
    got = rec["logits"]
    if run.args.variant == "control":
        got = reference.full_forward(model, rec["params"], rec["feats"],
                                     rec["idx"], rec["mask"],
                                     jnp.bfloat16).mean(axis=0)[rec["nodes"]]
    readings = checks.logit_readings(got, ref[rec["nodes"]])
    # the widest gap does not separate the control here (PERF.md section 7)
    del readings["logit_gap"]
    return checks.with_limits(readings, run.cell["name"])
