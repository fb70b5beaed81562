"""Host sampler: mean milliseconds of one ``sample_round`` of the trainer's
sampler (benchmark span ``sample``, run by the prefetch worker) among the
rounds sampled inside the window."""


def read(run):
    seconds, count = run.spans.total("sample", *run.window)
    return seconds / count * 1e3 if count else None
