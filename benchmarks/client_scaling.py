"""Paper Table 5 (Cent / StAl / GLASU across M = 3, 5, 7 clients) plus the
backend-scaling chart: per-round wall clock vs n_clients for the vmapped
(single-device stacked-axis) and sharded (one device per client,
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` on CPU) backends.

  PYTHONPATH=src python -m benchmarks.client_scaling --backend sharded
  PYTHONPATH=src XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python -m benchmarks.client_scaling --backend both --scaling-only
"""
import argparse

from repro.launch.compile_cache import enable_compile_cache

from .common import BenchSettings, csv, run_method


def run(dataset="citeseer", ms=(3, 5, 7), seeds=(0,), rounds=None,
        settings=None, backend="vmapped"):
    s = settings or BenchSettings()
    out = {}
    cent = run_method("cent", dataset, seed=seeds[0], s=s, rounds=rounds)
    csv(f"table5/{dataset}/cent", f"acc={cent.test_acc * 100:.1f}")
    for m in ms:
        for meth in ("stal", "glasu"):
            accs = []
            for seed in seeds:
                r = run_method(meth, dataset, n_clients=m, seed=seed, s=s,
                               q=1, rounds=rounds, backend=backend)
                accs.append(r.test_acc)
            acc = sum(accs) / len(accs)
            out[(m, meth)] = acc
            csv(f"table5/{dataset}/M={m}/{meth}",
                f"acc={acc * 100:.1f}", f"backend={backend}")
    return out


def run_scaling(dataset="citeseer", ms=(3, 5, 7), rounds=16, reps=3,
                backends=("vmapped", "sharded"), settings=None):
    """Per-round wall clock vs client count, per backend.

    Times the backends' scanned ``run_step`` directly (``rounds`` rounds per
    dispatch, best of ``reps``, compile excluded via one warmup call) — the
    same hot path the Trainer drives, without eval/prefetch noise. For the
    sharded backend the client-mesh device count rides along in the derived
    column, so the chart distinguishes real multi-device placement from the
    degenerate 1-device mesh (run under
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` to get one CPU
    device per client).
    """
    import time

    import jax
    import jax.numpy as jnp

    from repro.api import ExperimentConfig, make_backend
    from repro.core import glasu
    from repro.graph.prefetch import stack_rounds
    from repro.graph.sampler import GlasuSampler
    from repro.graph.synth import make_vfl_dataset

    s = settings or BenchSettings()
    out = {}
    for m in ms:
        cfg = ExperimentConfig(
            name=f"scaling-{dataset}-M{m}", dataset=dataset, n_clients=m,
            n_layers=s.n_layers, hidden=s.hidden, backbone=s.backbone,
            batch_size=s.batch_size, fanout=s.fanout, size_cap=s.size_cap,
            rounds=rounds, lr=s.lr)
        data = make_vfl_dataset(dataset, n_clients=m, seed=0)
        mcfg = cfg.glasu_config(data)
        sampler = GlasuSampler(data, cfg.sampler_config(), seed=0)
        params0 = glasu.init_params(jax.random.PRNGKey(0), mcfg)
        opt = cfg.make_optimizer()
        batches = jax.tree.map(
            jnp.asarray,
            stack_rounds([jax.tree.map(lambda x: x.copy(),
                                       sampler.sample_round())
                          for _ in range(rounds)]))
        keys = jax.vmap(jax.random.fold_in, in_axes=(None, 0))(
            jax.random.PRNGKey(1), jnp.arange(rounds))
        for backend in backends:
            b = make_backend(backend)
            b.bind(mcfg, opt, sampler)
            best = float("inf")
            for rep in range(reps + 1):       # rep 0 = compile warmup
                p = jax.tree.map(jnp.array, params0)   # run_step donates
                o = opt.init(p)
                t0 = time.perf_counter()
                res = b.run_step(p, o, batches, keys)
                jax.block_until_ready(res.losses)
                jax.block_until_ready(jax.tree.leaves(res.params)[0])
                if rep:
                    best = min(best, time.perf_counter() - t0)
            devices = (b.mesh.shape["clients"] if backend == "sharded"
                       else 1)
            s_round = best / rounds
            out[(m, backend)] = s_round
            csv(f"scaling/{dataset}/M={m}/{backend}",
                f"s_per_round={s_round:.5f}",
                f"devices={devices},comm_bytes={res.comm_bytes_round}")
    return out


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--dataset", default="citeseer")
    ap.add_argument("--backend", default="vmapped",
                    choices=("vmapped", "sharded", "both"))
    ap.add_argument("--ms", type=int, nargs="+", default=[3, 5, 7])
    ap.add_argument("--rounds", type=int, default=30)
    ap.add_argument("--scaling-only", action="store_true",
                    help="skip the Table-5 accuracy sweep")
    args = ap.parse_args()
    enable_compile_cache()

    backends = (("vmapped", "sharded") if args.backend == "both"
                else (args.backend,))
    if not args.scaling_only:
        for backend in backends:
            run(args.dataset, ms=tuple(args.ms), rounds=args.rounds,
                backend=backend)
    print("# scaling: per-round wall clock vs n_clients")
    run_scaling(args.dataset, ms=tuple(args.ms), rounds=args.rounds,
                backends=backends)


if __name__ == "__main__":
    main()
