"""The program's own spans (``repro.obs``) over a run's measured window.

A reader asks for one span's milliseconds per round of the window: the sum
of the seconds of the spans of that name that started in ``run.window`` on
one thread, over ``run.facts["rounds"]``. The main thread is the one that
ran ``Trainer.run`` (the thread of the window's ``step`` spans). Nothing is
reported when the program has no ``repro.obs`` (commits before it), when
the ring dropped spans from inside the window, or when the span is absent.
"""
from __future__ import annotations

from typing import Optional

PREFETCH_THREAD = "glasu-prefetch"


def ms_per_round(run, name: str,
                 thread: Optional[str] = None) -> Optional[float]:
    """Milliseconds per round in span ``name`` on ``thread`` (default: the
    thread of the window's ``step`` spans)."""
    try:
        from repro import obs
    except ImportError:
        return None
    rounds = run.facts.get("rounds")
    lo, hi = run.window
    if not rounds or not obs.complete(lo):
        return None
    spans = obs.records(lo, hi)
    if thread is None:
        threads = {s.thread for s in spans if s.name == "step"}
        if len(threads) != 1:
            return None
        thread = threads.pop()
    seconds = [s.t1 - s.t0 for s in spans
               if s.name == name and s.thread == thread]
    return sum(seconds) * 1e3 / rounds if seconds else None
