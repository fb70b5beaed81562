"""glint self-tests: every rule fires on a seeded-violation corpus and stays
quiet on a clean twin; the jaxpr contracts catch seeded f64 / broken-donation
/ meter-drift cases; the committed repo baseline is zero unsuppressed
findings; and the runtime guards actually guard.

The snippet corpus lives in string literals — the linter parses them as
stand-alone modules with repo-relative paths chosen to land inside (or
outside) the traced/hot prefixes each rule is gated on.
"""
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tools.glint import REPO, parse_suppressions, run_lint
from tools.glint import contracts
from tools.glint import rules as rules_mod
from tools.glint.pytest_plugin import RetraceGuard, jit_cache_size

TRACED = "src/repro/core/glasu.py"     # inside TRACED_PREFIXES
HOT = "src/repro/serve/hot.py"         # inside HOT_PREFIXES, not traced
COLD = "src/repro/launch/cold.py"      # neither


def lint(code, rule, rel=TRACED):
    """Run one rule over one dedented snippet; return its findings."""
    code = textwrap.dedent(code)
    active = rules_mod.resolve([rule])
    return rules_mod.check_file(Path("/snippet.py"), rel, code, active,
                                repo=REPO, all_files=())


def fired(code, rule, rel=TRACED):
    return [f for f in lint(code, rule, rel) if f.rule == rule]


# ================================================================ layer 1
# -------------------------------------------------------- GL000 + engine
def test_gl000_bare_suppression_is_a_finding(tmp_path):
    (tmp_path / "src").mkdir()
    (tmp_path / "src/a.py").write_text(
        "def f(x=[]):  # glint: disable=GL008\n    return x\n")
    findings, report = run_lint(roots=("src",), repo=tmp_path,
                                rules=["GL008"])
    assert [f.rule for f in findings] == ["GL000"]
    # the bare comment still suppresses — GL008 itself is NOT reported
    assert report["suppressed_findings"] == 1


def test_reasoned_suppression_silences_and_is_counted(tmp_path):
    (tmp_path / "src").mkdir()
    (tmp_path / "src/a.py").write_text(
        "def f(x=[]):  # glint: disable=GL008 shared sentinel, never mutated\n"
        "    return x\n")
    findings, report = run_lint(roots=("src",), repo=tmp_path,
                                rules=["GL008"])
    assert findings == []
    assert report["suppressed_findings"] == 1
    assert report["suppression_sites"] == 1


def test_file_level_suppression_covers_any_line():
    sup = parse_suppressions(
        "# glint: disable-file=GL009 corpus fixture\n\nx = 1\n")
    assert sup.covers("GL009", 3)
    assert not sup.covers("GL008", 3)


def test_suppression_on_wrong_line_does_not_cover():
    sup = parse_suppressions("x = 1  # glint: disable=GL008 why\ny = 2\n")
    assert sup.covers("GL008", 1)
    assert not sup.covers("GL008", 2)


# ---------------------------------------------------------------- GL001
def test_gl001_numpy_and_item_in_traced_module():
    code = """
    def round_body(h):
        a = np.sum(h)
        b = h.item()
        c = float(a)
        return a, b, c
    """
    lines = {f.line for f in fired(code, "GL001")}
    assert len(lines) == 3


def test_gl001_clean_statics_and_untraced_modules():
    code = """
    def round_body(h):
        dt = np.dtype("float32")
        n = int(h.shape[0])
        x = float(2.0)
        return dt, n, x
    """
    assert not fired(code, "GL001")
    assert not fired("def f(h):\n    return np.sum(h)\n", "GL001", rel=COLD)


# ---------------------------------------------------------------- GL002
def test_gl002_sample_then_reuse():
    code = """
    def f(key):
        a = jax.random.normal(key, (3,))
        b = jax.random.uniform(key, (3,))
        return a + b
    """
    assert fired(code, "GL002")


def test_gl002_sample_after_derive_and_double_split():
    consumed_after_derive = """
    def f(key):
        sub = jax.random.split(key, 2)
        x = jax.random.normal(key, (3,))
        return sub, x
    """
    assert fired(consumed_after_derive, "GL002")
    double_split = """
    def f(key):
        a = jax.random.split(key, 2)
        b = jax.random.split(key, 2)
        return a, b
    """
    # NOTE: assignment to a/b does not reset `key` tracking, only `key = ...`
    assert fired(double_split, "GL002")
    dup_fold = """
    def f(key):
        a = jax.random.fold_in(key, 0)
        b = jax.random.fold_in(key, 0)
        return a, b
    """
    assert fired(dup_fold, "GL002")


def test_gl002_clean_patterns():
    clean = """
    def f(key):
        mkey = jax.random.fold_in(key, 0)
        nkey = jax.random.fold_in(key, 1)
        a = jax.random.normal(mkey, (3,))
        return a, nkey

    def g(key):
        key, sub = jax.random.split(key)
        a = jax.random.normal(sub, (3,))
        key = jax.random.fold_in(key, 7)
        b = jax.random.normal(key, (3,))
        return a + b

    def h(key):
        stack = jax.vmap(lambda key: jax.random.normal(key, ()))(key)
        return jax.random.split(key, 2), stack
    """
    assert not fired(clean, "GL002")


# ---------------------------------------------------------------- GL003
def test_gl003_x64_attrs_strings_and_toggle():
    code = """
    import jax
    A = np.float64
    B = jnp.int64
    def f(x):
        return x.astype("float64")
    jax.config.update("jax_enable_x64", True)
    """
    assert len(fired(code, "GL003", rel=COLD)) == 4


def test_gl003_clean_32bit():
    code = "A = np.float32\nB = jnp.int32\nC = 'float32'\n"
    assert not fired(code, "GL003", rel=COLD)


# ---------------------------------------------------------------- GL004
def test_gl004_device_op_in_loop_in_hot_module():
    code = """
    def serve_step(xs):
        out = []
        for x in xs:
            out.append(jnp.dot(x, x))
        return out
    """
    assert fired(code, "GL004", rel=HOT)
    # same code outside the hot prefixes is fine
    assert not fired(code, "GL004", rel=COLD)


def test_gl004_clean_nested_def_and_host_loop():
    code = """
    def serve_step(xs):
        def body(c, x):
            return c, jnp.dot(x, x)
        total = 0
        for x in xs:
            total += len(x)
        return body, total
    """
    assert not fired(code, "GL004", rel=HOT)


# ---------------------------------------------------------------- GL005
def test_gl005_program_id():
    code = """
    def kernel(o_ref):
        i = pl.program_id(0)
        o_ref[i] = i
    """
    assert fired(code, "GL005", rel="src/repro/kernels/k.py")
    assert not fired("def kernel(o_ref):\n    o_ref[0] = 1\n", "GL005",
                     rel="src/repro/kernels/k.py")


# ---------------------------------------------------------------- GL006
_GL006_BAD = """
def call(x, block):
    return pl.pallas_call(kern, grid=(x.shape[0] // block,))(x)
"""


def test_gl006_floordiv_grid_without_guard():
    assert fired(_GL006_BAD, "GL006", rel="src/repro/kernels/k.py")


def test_gl006_clean_with_assert_or_pad():
    with_assert = """
    def call(x, block):
        assert x.shape[0] % block == 0
        return pl.pallas_call(kern, grid=(x.shape[0] // block,))(x)
    """
    assert not fired(with_assert, "GL006", rel="src/repro/kernels/k.py")
    with_pad = """
    def call(x, block):
        x = jnp.pad(x, ((0, (-x.shape[0]) % block), (0, 0)))
        return pl.pallas_call(kern, grid=(x.shape[0] // block,))(x)
    """
    assert not fired(with_pad, "GL006", rel="src/repro/kernels/k.py")


# ---------------------------------------------------------------- GL007
def test_gl007_blockspec_memory_space():
    bare = "spec = pl.BlockSpec((8, 8), lambda i: (i, 0))\n"
    assert fired(bare, "GL007", rel="src/repro/kernels/k.py")
    annotated = ("spec = pl.BlockSpec((8, 8), lambda i: (i, 0), "
                 "memory_space=pltpu.VMEM)\n")
    assert not fired(annotated, "GL007", rel="src/repro/kernels/k.py")


# ---------------------------------------------------------------- GL008
def test_gl008_mutable_defaults():
    code = """
    def f(a=[], b={}, *, c=set()):
        return a, b, c
    """
    assert len(fired(code, "GL008", rel=COLD)) == 3
    assert not fired("def f(a=None, b=()):\n    return a, b\n", "GL008",
                     rel=COLD)


# ---------------------------------------------------------------- GL009
def test_gl009_global_rng_and_unseeded():
    code = """
    import random
    def f():
        a = np.random.normal(size=3)
        rng = np.random.default_rng()
        b = random.randint(0, 9)
        return a, rng, b
    """
    assert len(fired(code, "GL009", rel=COLD)) == 3


def test_gl009_clean_seeded_generator():
    code = "rng = np.random.default_rng(0)\nx = rng.normal(size=3)\n"
    assert not fired(code, "GL009", rel=COLD)


# ---------------------------------------------------------------- GL010
def _write(root: Path, rel: str, text: str):
    p = root / rel
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(text)


def test_gl010_dead_module_flagged_imported_module_not(tmp_path):
    _write(tmp_path, "src/repro/dead.py", "X = 1\n")
    _write(tmp_path, "src/repro/used.py", "Y = 2\n")
    _write(tmp_path, "tests/test_t.py",
           "from repro.used import Y\nassert Y == 2\n")
    findings, _ = run_lint(roots=("src", "tests"), repo=tmp_path,
                           rules=["GL010"])
    assert [f.path for f in findings] == ["src/repro/dead.py"]


def test_gl010_entry_points_and_registry_suppressions_exempt(tmp_path):
    _write(tmp_path, "src/repro/cli.py",
           "def main():\n    pass\n\nif __name__ == '__main__':\n"
           "    main()\n")
    _write(tmp_path, "src/repro/plugin.py",
           "# glint: disable-file=GL010 loaded dynamically via registry\n"
           "X = 1\n")
    _write(tmp_path, "src/repro/__init__.py", "")
    findings, report = run_lint(roots=("src",), repo=tmp_path,
                                rules=["GL010"])
    assert findings == []
    assert report["suppressed_findings"] == 1


# ---------------------------------------------------------------- GL011
def test_gl011_unused_import():
    code = "import os\nimport sys\n\nprint(sys.argv)\n"
    got = fired(code, "GL011", rel=COLD)
    assert len(got) == 1 and "`os`" in got[0].message


def test_gl011_all_exports_and_doc_references_exempt():
    code = ('import os\nimport io\n\n__all__ = ["os"]\n\n'
            '"""uses ``io.BytesIO`` in doctests"""\n')
    assert not fired(code, "GL011", rel=COLD)
    init = "from .mod import thing\n"
    assert not fired(init, "GL011", rel="src/repro/pkg/__init__.py")


# ---------------------------------------------------------------- GL012
def test_gl012_swallowed_exception_fires():
    code = """
    def f():
        try:
            risky()
        except Exception:
            pass

    def g():
        try:
            risky()
        except:
            return {}
    """
    got = fired(code, "GL012", rel=COLD)
    assert len(got) == 2
    assert "swallows" in got[0].message
    assert "bare `except:`" in got[1].message


def test_gl012_clean_on_handled_exceptions():
    code = """
    import logging

    def reraise():
        try:
            risky()
        except Exception as e:
            raise RuntimeError("ctx") from e

    def logged():
        try:
            risky()
        except Exception:
            logging.warning("recoverable; continuing")

    def propagated(q):
        try:
            risky()
        except BaseException as e:
            q.put(e)            # exception object forwarded, not dropped

    def narrow():
        try:
            risky()
        except (ValueError, KeyError):
            return None         # narrow catch is deliberate handling
    """
    assert not fired(code, "GL012", rel=COLD)
    # rule is scoped to src/ — the same swallow in tests/tools is fine
    swallow = "try:\n    risky()\nexcept Exception:\n    pass\n"
    assert not fired(swallow, "GL012", rel="tests/test_x.py")


# ----------------------------------------------------- committed baseline
def test_repo_lint_baseline_is_clean():
    """The whole point: src/ + tests/ carry zero unsuppressed findings."""
    findings, report = run_lint()
    assert findings == [], "\n".join(f.format() for f in findings)
    assert report["files"] > 50


# ================================================================ layer 2
def test_gl201_catches_seeded_f64_trace():
    jax.config.update("jax_enable_x64", True)  # glint: disable=GL003 deliberately seeding an f64 trace for the checker-under-test
    try:
        closed = jax.make_jaxpr(lambda x: x * 2.0)(np.zeros(3, np.float64))  # glint: disable=GL003 the seeded f64 violation itself
    finally:
        jax.config.update("jax_enable_x64", False)  # glint: disable=GL003 restoring the repo-wide x64-off contract
    got = contracts._check_no_x64("seeded", closed, "x.py")
    assert got and got[0].rule == "GL201"


def test_gl201_clean_on_f32_trace():
    closed = jax.make_jaxpr(lambda x: x * 2.0)(np.zeros(3, np.float32))
    assert not contracts._check_no_x64("clean", closed, "x.py")


def test_gl202_catches_callback_primitives():
    def with_cb(x):
        return jax.pure_callback(
            lambda a: np.asarray(a), jax.ShapeDtypeStruct((3,), np.float32),
            x)
    closed = jax.make_jaxpr(with_cb)(np.zeros(3, np.float32))
    got = contracts._check_no_callbacks("seeded", closed, "x.py")
    assert got and got[0].rule == "GL202"
    clean = jax.make_jaxpr(lambda x: x + 1)(np.zeros(3, np.float32))
    assert not contracts._check_no_callbacks("clean", clean, "x.py")


def test_gl203_catches_broken_donation():
    def f(a, b):
        return a + 1.0, b * 2.0
    args = (jnp.ones((4,)), jnp.ones((4,)))
    undonated = jax.jit(f)
    got = contracts._check_donation("seeded", undonated, args, 2, "x.py")
    assert got and got[0].rule == "GL203"
    donated = jax.jit(f, donate_argnums=(0, 1))
    assert not contracts._check_donation("clean", donated, args, 2, "x.py")


def test_gl204_catches_meter_drift(monkeypatch):
    """Double every up_bytes the meter reports: the traced all_gather set no
    longer matches and the contract must fire."""
    glasu = contracts._fixture()["glasu"]
    orig = glasu.make_sharded_multi_round_fn

    def skewed(cfg, opt, mesh, record=None, **kw):
        wrapped = None if record is None else \
            (lambda r: record(r._replace(up_bytes=r.up_bytes * 2)))
        return orig(cfg, opt, mesh, record=wrapped, **kw)

    monkeypatch.setattr(glasu, "make_sharded_multi_round_fn", skewed)
    got = contracts._check_collectives_vs_meter()
    assert any(f.rule == "GL204" and "drifted" in f.message for f in got)


def test_gl204_catches_silent_meter(monkeypatch):
    glasu = contracts._fixture()["glasu"]
    orig = glasu.make_sharded_multi_round_fn

    def mute(cfg, opt, mesh, record=None, **kw):
        return orig(cfg, opt, mesh, record=None, **kw)

    monkeypatch.setattr(glasu, "make_sharded_multi_round_fn", mute)
    got = contracts._check_collectives_vs_meter()
    assert any(f.rule == "GL204" and "no collectives" in f.message
               for f in got)


def test_entry_point_registry_covers_public_builders():
    """Adding a public round/serve builder or Pallas kernel without
    registering it for contract checks is itself a failure."""
    import ast
    tree = ast.parse((REPO / "src/repro/core/glasu.py").read_text())
    public = {n.name for n in tree.body if isinstance(n, ast.FunctionDef)
              and n.name.startswith("make_") and n.name.endswith("_fn")}
    public |= {"serve_forward", "full_forward"}
    for p in sorted((REPO / "src/repro/kernels").glob("*.py")):
        kt = ast.parse(p.read_text())
        public |= {n.name for n in kt.body if isinstance(n, ast.FunctionDef)
                   and n.name.endswith("_pallas")
                   and not n.name.startswith("_")}
    missing = public - set(contracts.ENTRY_POINTS)
    assert not missing, f"unregistered entry points: {sorted(missing)}"


def test_repo_contracts_are_clean():
    findings, report = contracts.run_contracts()
    assert findings == [], "\n".join(f.format() for f in findings)
    assert "collectives-vs-meter" in report["entry_points"]


# ================================================================ layer 3
def test_retrace_guard_passes_on_stable_signature():
    fn = jax.jit(lambda x: x * 2.0)
    fn(jnp.ones((4,)))                       # warmup compile
    guard = RetraceGuard()
    guard.watch(fn, "stable")
    fn(jnp.ones((4,)))                       # cache hit
    guard.check()


def test_retrace_guard_fails_on_retrace():
    fn = jax.jit(lambda x: x * 3.0)
    fn(jnp.ones((4,)))
    guard = RetraceGuard()
    guard.watch(fn, "hot")
    fn(jnp.ones((5,)))                       # new shape -> recompile
    with pytest.raises(pytest.fail.Exception, match="retraced"):
        guard.check()


def test_retrace_guard_max_new_budget():
    fn = jax.jit(lambda x: x - 1.0)
    fn(jnp.ones((4,)))
    guard = RetraceGuard()
    guard.watch(fn, "warming", max_new=1)
    fn(jnp.ones((6,)))                       # one allowed recompile
    guard.check()


def test_jit_cache_size_rejects_plain_functions():
    with pytest.raises(TypeError, match="_cache_size"):
        jit_cache_size(lambda x: x)


def test_transfer_guard_blocks_implicit_transfers(transfer_guard):
    x = jnp.ones((4,), jnp.float32)
    host = np.arange(4, dtype=np.float32)
    with transfer_guard():
        with pytest.raises(Exception, match="[Dd]isallowed"):
            _ = x + host                     # implicit host->device upload
    _ = x + host                             # fine outside the scope


def test_transfer_guard_allows_explicit_staging(transfer_guard):
    host = np.ones(3, np.float32)
    with transfer_guard():
        staged = jnp.asarray(host)           # explicit stage-in
        _ = np.asarray(staged)               # explicit readback
