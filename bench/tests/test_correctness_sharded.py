"""The sharded cell ``cora-gcnii-m4.train-sharded`` on four CPU devices (the
harness's look for a chip skipped), at Cora's full size with windows of a
fraction of a second: the program reads ``correct`` true; the bfloat16
control and each fault planted in its timed path read false. Its readers
of the program's ``place`` span and ``exchange_bytes`` counter read what
the backend did, and nothing on a program without them.
"""
import collections
import time
from types import SimpleNamespace

import pytest

from bench import harness
from repro import obs
from repro.api import backends

CELL = "cora-gcnii-m4.train-sharded"
FAULTS = ("unchanged", "half_batch", "no_exchange")


def _run(variant: str, seed: int = 2147483653) -> dict:
    args = harness.parse(["--workload", CELL, "--seed", str(seed),
                          "--seconds", "0.3"])
    args.variant = variant
    return harness.execute(args, time.perf_counter(), rehearsal=True)


@pytest.fixture(scope="module")
def program():
    """The program's run, with the recorder emptied first and the bound
    backend kept."""
    bound = []
    bind = backends.ShardedBackend.bind

    def spy(self, *a, **kw):
        bind(self, *a, **kw)
        bound.append(self)

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(backends.ShardedBackend, "bind", spy)
        obs.reset()
        line = _run("program")
    backend, = bound
    return SimpleNamespace(line=line, backend=backend,
                           counters=obs.counters())


def test_program_is_correct(program):
    assert program.line["correct"], program.line["checks"]
    assert program.line["failed"] == 0 and program.line["attempted"] > 0
    assert program.line["device"]["count"] == 4
    assert dict(program.backend.mesh.shape) == {"clients": 4}


def test_exchange_reader_equals_audited_bytes(program, monkeypatch):
    monkeypatch.setattr(obs, "_counters",
                        collections.defaultdict(int, program.counters))
    got = harness._load_reader("sharded.exchange_bytes_per_round")(None)
    assert got == program.backend.bytes_per_round > 0


@pytest.mark.parametrize("variant", ("control",) + FAULTS)
def test_control_and_faults_are_not_correct(variant):
    line = _run(variant)
    assert not line["correct"], line["checks"]


def test_place_reader_sums_its_span_per_round(monkeypatch):
    """Two 10 ms steps in the window, each with a 1.5 ms ``place`` on the
    main thread: 1.5 ms a round; the prefetch worker's spans do not count."""
    ring = collections.deque(maxlen=obs.RING)
    for i in range(2):
        t = 100.0 + 0.010 * i
        ring.extend([
            obs.Span("place", "MainThread", t + 0.004, t + 0.0055,
                     "dispatch", i + 1),
            obs.Span("place", "glasu-prefetch", t, t + 0.005, "fill", i + 1),
            obs.Span("step", "MainThread", t, t + 0.010, None, i + 1)])
    monkeypatch.setattr(obs, "_ring", ring)
    monkeypatch.setattr(obs, "_evicted_t0", float("-inf"))
    run = SimpleNamespace(window=(100.0, 100.02), facts={"rounds": 2})
    got = harness._load_reader("sharded.place_ms_per_round")(run)
    assert got == pytest.approx(1.5)


@pytest.mark.parametrize("metric", ["sharded.place_ms_per_round",
                                    "sharded.exchange_bytes_per_round"])
def test_readers_say_nothing_without_the_program_s_records(monkeypatch,
                                                           metric):
    """A program before the sharded backend's span and counter (the
    parent of this cell's first commit) runs the cell; its line leaves
    these metrics out."""
    monkeypatch.setattr(obs, "_ring", collections.deque(maxlen=obs.RING))
    monkeypatch.setattr(obs, "_evicted_t0", float("-inf"))
    monkeypatch.setattr(obs, "_counters",
                        collections.defaultdict(int, {"rounds": 20}))
    run = SimpleNamespace(window=(100.0, 100.02), facts={"rounds": 2})
    assert harness._load_reader(metric)(run) is None
