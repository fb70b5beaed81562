"""Serving session: median ``ServeAnswer.latency_s`` of the window's cold
dispatches, in milliseconds."""
import statistics


def read(run):
    cold = run.facts.get("cold_dispatches")
    return statistics.median(cold) * 1e3 if cold else None
