"""Host sampler, as the main thread feels it: milliseconds per round of the
window that the thread running ``Trainer.run`` waits in
``PrefetchSampler.get`` for the prefetch worker's next step (program span
``wait_sample``)."""
from bench import program_spans


def read(run):
    return program_spans.ms_per_round(run, "wait_sample")
