"""Full-graph pass on the device: the least time the chip could take for
one pass (the larger of its FLOPs over the peak FLOP rate and its bytes
over the peak HBM rate, from ``flops.infer_pass``) over the device busy
time per pass in the traced window."""


def read(run):
    passes, t = run.facts.get("passes"), run.trace
    if not passes or t is None or t.busy_s <= 0:
        return None
    peaks = run.peaks()
    least = max(run.facts["pass_flops"] / peaks["flops_per_s"],
                run.facts["pass_bytes"] / peaks["hbm_bytes_per_s"])
    return 100.0 * least / (t.busy_s / passes)
