"""Whole pass: the FLOPs one exact full-graph pass needs times passes per
second over the window, as a share of the chip's peak."""


def read(run):
    passes = run.facts.get("passes")
    if not passes:
        return None
    peak = run.peaks()["flops_per_s"] * len(run.devices)
    return 100.0 * run.facts["pass_flops"] * passes / run.window_s / peak
