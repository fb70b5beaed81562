"""Micro-batcher: mean queries per dispatch over the window."""


def read(run):
    n = run.facts.get("dispatches")
    return run.facts["requests"] / n if n else None
