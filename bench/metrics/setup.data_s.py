"""Set-up: seconds of the benchmark span ``data`` around the dataset build
or load and the staging of its tables."""


def read(run):
    seconds, count = run.spans.total("data")
    return seconds if count else None
