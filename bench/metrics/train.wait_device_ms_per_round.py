"""Device, as the main thread feels it: milliseconds per round of the
window that the thread running ``Trainer.run`` blocks in
``PrefetchSampler.retire`` on the step before the one it just dispatched
(program span ``wait_device``)."""
from bench import program_spans


def read(run):
    return program_spans.ms_per_round(run, "wait_device")
