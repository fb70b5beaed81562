"""The readers of the program's own spans (``bench/metrics/train.*``
through ``bench/program_spans.py``), on a hand-built run and ring whose
answer is known."""
import collections
from types import SimpleNamespace

import pytest

from bench import harness
from repro import obs

MAIN, WORKER = "MainThread", "glasu-prefetch"
READERS = {"train.wait_sample_ms_per_round": "wait_sample",
           "train.stage_ms_per_round": "stage",
           "train.dispatch_ms_per_round": "dispatch",
           "train.wait_device_ms_per_round": "wait_device",
           "train.fill_wait_ms_per_round": "wait_free"}


def _step(t, key, spans):
    """One 10 ms step at ``t``: the main thread's five spans inside
    ``step``, the worker's ``fill`` around ``wait_free`` and ``sample``."""
    out = [obs.Span("step", MAIN, t, t + 0.010, None, key)]
    for name, a, b in spans:
        thread = WORKER if name in ("wait_free", "sample") else MAIN
        parent = "fill" if thread == WORKER else "step"
        out.append(obs.Span(name, thread, t + a, t + b, parent, key))
    out.append(obs.Span("fill", WORKER, t, t + 0.010, None, key))
    return out


# (name, start, end) within a step, in seconds after its start
STEP = [("wait_sample", 0.0, 0.004), ("stage", 0.004, 0.005),
        ("dispatch", 0.005, 0.009), ("wait_device", 0.009, 0.0092),
        ("hook", 0.0092, 0.0099), ("wait_free", 0.0, 0.0005),
        ("sample", 0.0005, 0.006)]
WANT_MS = {"wait_sample": 4.0, "stage": 1.0, "dispatch": 4.0,
           "wait_device": 0.2, "wait_free": 0.5}


@pytest.fixture
def ring(monkeypatch):
    """Five steps from t = 100 s; the window holds the last four."""
    ring = collections.deque(maxlen=obs.RING)
    for i in range(5):
        ring.extend(_step(100.0 + 0.010 * i, i + 1, STEP))
    monkeypatch.setattr(obs, "_ring", ring)
    monkeypatch.setattr(obs, "_evicted_t0", float("-inf"))
    return ring


def _run(rounds=4, window=(100.0095, 100.05)):
    return SimpleNamespace(window=window, facts={"rounds": rounds})


@pytest.mark.parametrize("metric", sorted(READERS))
def test_reader_sums_its_span_per_round(ring, metric):
    got = harness._load_reader(metric)(_run())
    assert got == pytest.approx(WANT_MS[READERS[metric]])


@pytest.mark.parametrize("metric", sorted(READERS))
def test_reader_is_silent_when_the_ring_dropped_window_spans(
        ring, monkeypatch, metric):
    read = harness._load_reader(metric)
    # a drop before the window leaves it whole
    monkeypatch.setattr(obs, "_evicted_t0", 100.005)
    assert read(_run()) == pytest.approx(WANT_MS[READERS[metric]])
    monkeypatch.setattr(obs, "_evicted_t0", 100.02)
    assert read(_run()) is None


def test_ring_overflow_marks_the_window_incomplete(monkeypatch):
    monkeypatch.setattr(obs, "_ring", collections.deque(maxlen=3 * 9))
    monkeypatch.setattr(obs, "_evicted_t0", float("-inf"))
    for i in range(5):
        for s in _step(100.0 + 0.010 * i, i + 1, STEP):
            obs._append(s)
    read = harness._load_reader("train.dispatch_ms_per_round")
    assert read(_run()) is None
    assert read(_run(rounds=2, window=(100.03, 100.05))) == pytest.approx(4.0)


def test_readers_are_silent_without_rounds_or_spans(ring):
    for metric in READERS:
        read = harness._load_reader(metric)
        assert read(_run(rounds=0)) is None
        assert read(_run(window=(200.0, 201.0))) is None


def test_main_thread_is_the_thread_of_the_step_spans(ring):
    # the same spans under another name for the main thread
    renamed = [s._replace(thread="trainer") if s.thread == MAIN else s
               for s in ring]
    ring.clear()
    ring.extend(renamed)
    read = harness._load_reader("train.stage_ms_per_round")
    assert read(_run()) == pytest.approx(1.0)
