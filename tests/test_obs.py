"""The program's span and counter recorder (``repro.obs``) and the spans
the training loop, the prefetch worker and the serving path put in it."""
import collections
import sys
import threading

import jax
import numpy as np
import pytest

from repro import obs
from repro.api import ExperimentConfig, Trainer

MAIN_SPANS = ("wait_sample", "stage", "dispatch", "wait_device", "hook")


@pytest.fixture(autouse=True)
def _empty_recorder():
    obs.reset()
    yield
    obs.reset()


def test_nested_spans_record_parents_keys_and_threads():
    with obs.span("outer", key=7):
        with obs.span("inner"):
            pass
        with obs.span("other", key=9):
            pass

    def work():
        with obs.span("worker"):
            pass

    t = threading.Thread(target=work, name="obs-test-worker")
    t.start()
    t.join(timeout=10)
    assert not t.is_alive()
    got = {s.name: s for s in obs.records()}
    me = threading.current_thread().name
    assert got["outer"] == (
        "outer", me, got["outer"].t0, got["outer"].t1, None, 7)
    assert got["inner"].parent == "outer" and got["inner"].key == 7
    assert got["other"].parent == "outer" and got["other"].key == 9
    assert got["worker"].thread == "obs-test-worker"
    assert got["worker"].parent is None
    outer, inner = got["outer"], got["inner"]
    assert outer.t0 <= inner.t0 <= inner.t1 <= outer.t1
    # records are ordered by end; the window selects on start
    assert [s.name for s in obs.records()][:3] == ["inner", "other", "outer"]
    assert [s.name for s in obs.records(inner.t0, inner.t1)] == ["inner"]


def test_span_is_recorded_when_its_body_raises():
    with pytest.raises(ValueError):
        with obs.span("boom"):
            raise ValueError
    with obs.span("after"):
        pass
    assert [(s.name, s.parent) for s in obs.records()] == [
        ("boom", None), ("after", None)]


def test_explicit_record_takes_the_open_span_as_parent():
    with obs.span("dispatching"):
        obs.record("queue", 1.0, 2.0, key=3)
    q = obs.records(0.5, 1.5)
    assert q == [obs.Span("queue", threading.current_thread().name,
                          1.0, 2.0, "dispatching", 3)]


def test_ring_is_bounded_and_says_what_it_dropped(monkeypatch):
    monkeypatch.setattr(obs, "_ring", collections.deque(maxlen=4))
    starts = []
    for i in range(6):
        with obs.span(f"s{i}") as s:
            pass
        starts.append(s._t0)
    assert [s.name for s in obs.records()] == ["s2", "s3", "s4", "s5"]
    # s1 was dropped: a window from s1 on misses it, one from s2 does not
    assert not obs.complete(starts[1])
    assert obs.complete(starts[2])
    obs.reset()
    assert obs.records() == [] and obs.complete(starts[0])


def test_counters_under_two_threads():
    n = 20_000
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=lambda: [obs.count("hits")
                                                    for _ in range(n)])
                   for _ in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads)
    obs.count("bytes", 2.5)
    assert obs.counters() == {"hits": 2 * n, "bytes": 2.5}
    snap = obs.counters()
    obs.count("hits")
    assert snap["hits"] == 2 * n       # a snapshot, not a live view


def test_compile_is_charged_to_the_innermost_span():
    # a constant no other test uses, so this call compiles here
    fn = jax.jit(lambda x: x * 3.25 + 0.125)
    with obs.span("outer"):
        with obs.span("compiling"):
            fn(np.float32(2.0)).block_until_ready()
        fn(np.float32(3.0)).block_until_ready()       # cached: no compile
    c = obs.counters()
    assert c["compiles_under:compiling"] == 1
    assert "compiles_under:outer" not in c
    assert c["compiles"] >= 1 and c["compile_s"] > 0


def _spans_by_step(spans, thread):
    steps = collections.defaultdict(list)
    for s in spans:
        if s.thread == thread:
            steps[s.key].append(s)
    return steps


@pytest.mark.parametrize("rounds_per_step", [1, 2])
def test_trainer_records_each_step(rounds_per_step):
    cfg = ExperimentConfig(name="obs", dataset="tiny", hidden=16,
                           batch_size=8, size_cap=96,
                           rounds=3 * rounds_per_step, eval_every=0,
                           rounds_per_step=rounds_per_step)
    staged = []

    class Staged:
        """Batches as the backend receives them: their bytes per step."""

        def __init__(self, backend):
            self.backend = backend

        def __getattr__(self, name):
            return getattr(self.backend, name)

        def run_step(self, params, opt_state, batches, keys, **kw):
            staged.append(sum(x.nbytes for x in jax.tree.leaves(batches)))
            return self.backend.run_step(params, opt_state, batches, keys,
                                         **kw)

    trainer = Trainer(cfg)
    trainer.backend = Staged(trainer.backend)
    trainer.run()
    main = threading.current_thread().name
    spans = obs.records()
    firsts = [1 + i * rounds_per_step for i in range(3)]

    by_step = _spans_by_step(spans, main)
    assert sorted(by_step) == firsts
    for key in firsts:
        step = by_step[key]
        names = [s.name for s in sorted(step, key=lambda s: s.t0)]
        # the first step has nothing in flight to wait for
        want = ["step"] + [n for n in MAIN_SPANS
                           if n != "wait_device" or key != firsts[0]]
        assert names == want
        outer = next(s for s in step if s.name == "step")
        assert all(s.parent == "step" for s in step if s is not outer)
        inner = sum(s.t1 - s.t0 for s in step if s is not outer)
        assert inner >= 0.9 * (outer.t1 - outer.t0)

    worker = _spans_by_step(spans, "glasu-prefetch")
    assert sorted(worker) == firsts
    for key in firsts:
        fill = worker[key]
        assert sorted(s.name for s in fill) == sorted(
            ["fill", "wait_free"] + ["sample"] * rounds_per_step)
        assert all(s.parent == "fill" for s in fill if s.name != "fill")

    c = obs.counters()
    assert c["rounds"] == 3 * rounds_per_step
    assert c["stage_bytes"] == sum(staged) > 0


def test_serving_records_answer_spans_and_queue_waits():
    from repro.core import glasu
    from repro.serve import InferenceSession, MicroBatcher, ServeConfig

    cfg = ExperimentConfig(name="obs-serve", dataset="tiny", hidden=16,
                           batch_size=8, size_cap=96)
    session = InferenceSession(None, cfg, serve=ServeConfig(max_batch=8))
    session.params = glasu.init_params(jax.random.PRNGKey(0), session.mcfg)
    with MicroBatcher(session, deadline_ms=1.0) as batcher:
        cold = batcher.query([1, 2, 3], timeout=60)
        warm = batcher.query([1, 2, 3], timeout=60)
    assert cold.cold and not warm.cold
    spans = obs.records()
    answers = sorted((s for s in spans if s.name == "answer"),
                     key=lambda s: s.t0)
    assert len(answers) == 2
    for a, want in zip(answers, (["cache", "plan", "forward", "classify"],
                                 ["cache", "classify"])):
        inner = sorted((s for s in spans if s.parent == "answer"
                        and a.t0 <= s.t0 and s.t1 <= a.t1),
                       key=lambda s: s.t0)
        assert [s.name for s in inner] == want
        assert all(s.thread == a.thread for s in inner)
    queue = sorted((s for s in spans if s.name == "queue"),
                   key=lambda s: s.t0)
    assert [q.key for q in queue] == [1, 2]
    for q, a in zip(queue, answers):
        assert q.t0 <= q.t1 <= a.t0
    assert obs.counters().get("compiles_under:forward", 0) >= 1
