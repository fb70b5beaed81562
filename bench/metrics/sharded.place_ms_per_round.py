"""Mesh placement: milliseconds per round of the window that the thread
running ``Trainer.run`` spends in ``ShardedBackend``'s placements of a
step onto the client mesh (params, optimizer state, the staged batch and
the carries; program span ``place``)."""
from bench import program_spans


def read(run):
    return program_spans.ms_per_round(run, "place")
