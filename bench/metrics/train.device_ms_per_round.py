"""Round engine on the device: device busy milliseconds in the traced
window per round completed in it."""


def read(run):
    rounds = run.facts.get("rounds")
    if not rounds or run.trace is None or run.trace.busy_s <= 0:
        return None
    return run.trace.busy_s * 1e3 / rounds
