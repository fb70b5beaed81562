"""Whole round step on the client mesh: the FLOPs one round needs
(``flops.train_round``, every party) times rounds per second over the
window, as a share of the peak of all the cell's chips."""


def read(run):
    rounds, flops = run.facts.get("rounds"), run.facts.get("flops_per_round")
    if not rounds or not flops:
        return None
    peak = run.peaks()["flops_per_s"] * len(run.devices)
    return 100.0 * flops * rounds / run.window_s / peak
