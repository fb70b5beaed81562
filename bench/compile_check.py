"""Compile one cell's device program for a described TPU v5e chip, with no
chip attached, and print its memory analysis.

    JAX_PLATFORMS=cpu python3 bench/compile_check.py --workload reddit-gcnii.infer

Only the ``infer`` traffic is covered: one exact full-graph pass at the
configuration's full size, as ``drivers/infer.py`` jits it. A compile that
passes here is not a chip run.
"""
import os
import sys
import time
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv):
    import argparse
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from bench import data, harness, reference
    from repro.core import glasu

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    a = ap.parse_args(argv)
    _, _, cfg = harness.cell_spec(a.workload)
    m, d = cfg["model"], cfg["data"]
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    blocks = data.feature_blocks(d["feat_dim"], m["n_clients"])
    d_in = max(hi - lo for lo, hi in blocks)
    n, w = d["n_nodes"], cfg["eval_table_cap"] + 1
    mcfg = glasu.GlasuConfig(
        n_clients=m["n_clients"], n_layers=m["n_layers"], hidden=m["hidden"],
        n_classes=d["n_classes"], d_in=d_in, backbone=m["backbone"],
        agg=m["agg"], agg_layers=tuple(reference.agg_layers(m)),
        gcnii_alpha=m["gcnii_alpha"], gcnii_beta=m["gcnii_beta"])
    p_abs = jax.eval_shape(lambda: reference.init_params(
        0, m, d_in, d["n_classes"]))
    sds = lambda s, dt: jax.ShapeDtypeStruct(s, dt, sharding=one)
    p_abs = jax.tree.map(lambda x: sds(x.shape, x.dtype), p_abs)
    args = (p_abs, sds((m["n_clients"], n, d_in), jnp.float32),
            sds((m["n_clients"], n, w), jnp.int32),
            sds((m["n_clients"], n, w), jnp.float32))
    fn = jax.jit(lambda p, f, i, k: glasu.full_forward(
        p, mcfg, f, i, k, chunk=min(4096, n)))
    t0 = time.perf_counter()
    compiled = fn.lower(*args).compile()
    print(f"{a.workload}: compiled for v5e in "
          f"{time.perf_counter() - t0:.1f} s")
    print(compiled.memory_analysis())
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
