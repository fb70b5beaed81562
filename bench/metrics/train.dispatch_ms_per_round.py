"""Round engine, host side: milliseconds per round of the window that the
thread running ``Trainer.run`` spends in ``Trainer._run_step``, enqueueing
the jitted multi-round step (program span ``dispatch``)."""
from bench import program_spans


def read(run):
    return program_spans.ms_per_round(run, "dispatch")
