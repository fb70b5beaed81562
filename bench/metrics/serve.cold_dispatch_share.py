"""Serving session: share of the window's dispatches that were cold
(``ServeAnswer.cold``: a plan built and the layer stack run)."""


def read(run):
    n = run.facts.get("dispatches")
    if not n:
        return None
    return 100.0 * len(run.facts["cold_dispatches"]) / n
