"""Host sampler, idle: milliseconds per round of the window that the
prefetch worker (thread ``glasu-prefetch``) waits for a free generation
buffer because every one is in flight (program span ``wait_free``). Near
zero when the sampler sets the pace; large when the main thread does."""
from bench import program_spans


def read(run):
    return program_spans.ms_per_round(run, "wait_free",
                                      program_spans.PREFETCH_THREAD)
