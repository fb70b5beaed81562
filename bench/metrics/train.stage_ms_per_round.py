"""Input staging: milliseconds per round of the window that the thread
running ``Trainer.run`` spends folding a step's round keys, drawing its
faults and handing its batch to ``jax.device_put`` (program span
``stage``)."""
from bench import program_spans


def read(run):
    return program_spans.ms_per_round(run, "stage")
