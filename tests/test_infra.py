"""Infrastructure tests: checkpointing, sharding-spec inference, HLO walker."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import checkpoint
from repro.launch import hlo_cost
from repro.launch.compile_cache import REPO_CACHE_DIR, enable_compile_cache


# ------------------------------------------------------------- checkpointing
def test_checkpoint_roundtrip(tmp_path):
    tree = {"w": jnp.asarray(np.random.default_rng(0).normal(size=(4, 3)),
                             jnp.bfloat16),
            "b": jnp.arange(5, dtype=jnp.int32),
            "nested": {"s": jnp.asarray(3.5, jnp.float32)}}
    checkpoint.save(str(tmp_path), 7, tree)
    assert checkpoint.latest_step(str(tmp_path)) == 7
    back = checkpoint.restore(str(tmp_path), tree)
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(back)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def test_restore_missing_file_raises_filenotfound(tmp_path):
    tree = {"x": jnp.zeros((2,))}
    with pytest.raises(FileNotFoundError, match="no checkpoint"):
        checkpoint.restore(str(tmp_path), tree)
    checkpoint.save(str(tmp_path), 1, tree)
    # a step-aligned sidecar that was never written must fail loudly too,
    # listing what exists — not fall back to zeros or the main ckpt
    with pytest.raises(FileNotFoundError, match="comp"):
        checkpoint.restore(str(tmp_path), tree, name="comp")


@pytest.mark.parametrize("name", ["ckpt", "comp", "fault"])
def test_restore_truncated_npz_raises_loud(tmp_path, name):
    """A half-written file (killed mid-save) must raise RuntimeError naming
    the file — the main checkpoint and every sidecar kind (EF accumulators,
    the fault-tolerant stale-embedding cache) share the contract."""
    from pathlib import Path
    tree = {"x": jnp.arange(512, dtype=jnp.float32)}
    fn = Path(checkpoint.save(str(tmp_path), 3, tree, name=name))
    raw = fn.read_bytes()
    fn.write_bytes(raw[:len(raw) // 2])
    with pytest.raises(RuntimeError, match="corrupt checkpoint"):
        checkpoint.restore(str(tmp_path), tree, step=3, name=name)


def test_restore_garbled_npz_raises_loud(tmp_path):
    from pathlib import Path
    tree = {"x": jnp.zeros((4,))}
    fn = Path(checkpoint.save(str(tmp_path), 2, tree))
    fn.write_bytes(b"\x89not-a-zip" * 64)
    with pytest.raises(RuntimeError, match="corrupt checkpoint"):
        checkpoint.restore(str(tmp_path), tree)


def test_restore_leaf_count_mismatch_raises_runtime(tmp_path):
    checkpoint.save(str(tmp_path), 1, {"x": jnp.zeros((2,))})
    like = {"x": jnp.zeros((2,)), "y": jnp.zeros((3,))}
    with pytest.raises(RuntimeError, match="leaves"):
        checkpoint.restore(str(tmp_path), like)


def test_checkpoint_cleanup(tmp_path):
    tree = {"x": jnp.zeros((2,))}
    for s in range(5):
        checkpoint.save(str(tmp_path), s, tree)
    checkpoint.cleanup(str(tmp_path), keep=2)
    import glob
    assert len(glob.glob(str(tmp_path / "ckpt_*.npz"))) == 2
    assert checkpoint.latest_step(str(tmp_path)) == 4


# -------------------------------------------------------- sharding inference
def test_param_specs_rules():
    from jax.sharding import PartitionSpec as P
    from repro.launch import sharding as shd
    mesh = jax.make_mesh((1, 1), ("data", "model"))

    # stand-in leaves (ShapeDtypeStruct is enough for the rule engine)
    sds = jax.ShapeDtypeStruct
    params = {
        "emb": sds((1024, 64), jnp.float32),
        "blocks": {"attn": {"wq": sds((8, 64, 128), jnp.float32),
                            "wo": sds((8, 128, 64), jnp.float32)},
                   "moe": {"w_gate": sds((8, 4, 64, 32), jnp.float32),
                           "router": sds((64, 4), jnp.float32)}},
        "final_norm": {"g": sds((64,), jnp.float32)},
    }
    specs = shd.param_specs(params, mesh)
    # mesh axes have size 1 -> guard strips everything to None; use a fake
    # 4-device mesh shape instead via the internal rule function
    raw = jax.tree_util.tree_map_with_path(
        lambda p, l: shd._leaf_spec(p, l, FakeMesh()), params)
    # small leaves: pure TP rules, no FSDP (below the 16 MB threshold)
    assert raw["emb"] == P("model", None)
    assert raw["blocks"]["attn"]["wq"] == P(None, None, "model")
    assert raw["blocks"]["attn"]["wo"] == P(None, "model", None)
    assert raw["blocks"]["moe"]["w_gate"][1] == "model"   # expert axis
    assert raw["blocks"]["moe"]["router"] == P(None, None)
    assert raw["final_norm"]["g"] == P(None)
    # large leaf: FSDP adds 'data' on the biggest free dim
    big = jax.ShapeDtypeStruct((32, 8192, 8192), jnp.float32)
    spec = shd._leaf_spec(
        (jax.tree_util.DictKey("blocks"), jax.tree_util.DictKey("wq")),
        big, FakeMesh())
    assert spec == P(None, "data", "model")


class FakeMesh:
    axis_names = ("data", "model")
    shape = {"data": 2, "model": 2}


def test_opt_state_specs_structural():
    from jax.sharding import PartitionSpec as P
    from repro.launch import sharding as shd
    from repro.optim import optimizers as opt_lib
    params = {"w": jnp.zeros((8, 4)), "g": jnp.zeros((4,))}
    pspecs = {"w": P("data", "model"), "g": P(None)}
    for make in (lambda: opt_lib.adamw(1e-3), lambda: opt_lib.adafactor(1e-3),
                 lambda: opt_lib.sgd(1e-3, momentum=0.9)):
        opt = make()
        state = jax.eval_shape(opt.init, params)
        mesh = jax.make_mesh((1, 1), ("data", "model"))
        specs = shd.opt_state_specs(state, pspecs, mesh)
        # structurally mappable onto the state (would raise otherwise)
        jax.tree.flatten(specs)


# ----------------------------------------------------------------- HLO walker
def test_hlo_walker_counts_scan_trips():
    def ten(x):
        def body(c, _):
            return c @ c, None
        y, _ = jax.lax.scan(body, x, None, length=10)
        return y

    x = jnp.zeros((128, 128))
    r = hlo_cost.analyze(jax.jit(ten).lower(x).compile().as_text())
    assert r["flops"] == pytest.approx(10 * 2 * 128 ** 3, rel=0.01)


def test_hlo_walker_nested_and_collect_bytes():
    def nested(x):
        def outer(c, _):
            def inner(cc, _):
                return cc @ cc, None
            y, _ = jax.lax.scan(inner, c, None, length=5)
            return y, None
        y, _ = jax.lax.scan(outer, x, None, length=3)
        return y

    x = jnp.zeros((64, 64))
    r = hlo_cost.analyze(jax.jit(nested).lower(x).compile().as_text())
    assert r["flops"] == pytest.approx(15 * 2 * 64 ** 3, rel=0.01)
    assert r["hbm_bytes"] > 15 * 2 * 64 * 64 * 4  # at least the carrier traffic


# ------------------------------------------------------------ dryrun env hygiene
@pytest.mark.slow
def test_dryrun_appends_to_user_xla_flags():
    """Importing launch.dryrun must append its host-device-count flag to any
    user-set XLA_FLAGS (it used to clobber the variable), and must respect a
    user-chosen device count."""
    import os
    import subprocess
    import sys

    code = ("import os, repro.launch.dryrun; "
            "print(os.environ['XLA_FLAGS'])")
    env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_cpu_enable_fast_math=false")
    out = subprocess.run([sys.executable, "-c", code], env=env, cwd=".",
                         capture_output=True, text=True, check=True).stdout
    assert "--xla_cpu_enable_fast_math=false" in out
    assert "--xla_force_host_platform_device_count=512" in out

    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    out = subprocess.run([sys.executable, "-c", code], env=env, cwd=".",
                         capture_output=True, text=True, check=True).stdout
    assert out.strip() == "--xla_force_host_platform_device_count=4"


# --------------------------------------------------------- compile cache
@pytest.mark.parametrize("env_dir", [None, "from_env"])
def test_enable_compile_cache_dir(monkeypatch, tmp_path, env_dir):
    """``$JAX_COMPILATION_CACHE_DIR`` wins when set; otherwise the cache
    sits at the fixed ``<repo>/.jax_cache`` (never a per-run path)."""
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = str(REPO_CACHE_DIR)
        assert REPO_CACHE_DIR.parent.joinpath("chip_smoke.py").is_file()
    else:
        want = str(tmp_path / env_dir)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", want)
    before = jax.config.jax_compilation_cache_dir
    try:
        assert enable_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
