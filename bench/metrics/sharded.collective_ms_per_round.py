"""Aggregation exchange on the device: milliseconds per round of the
traced window in collective operations (``TraceSummary.collective_s``,
the union of their intervals, mean over the cell's chips)."""


def read(run):
    rounds = run.facts.get("rounds")
    if not rounds or run.trace is None or run.trace.collective_s <= 0:
        return None
    return run.trace.collective_s * 1e3 / rounds
