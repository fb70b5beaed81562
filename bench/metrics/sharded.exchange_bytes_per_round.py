"""Aggregation exchange: bytes of the exchange between parties per round,
the program's counter ``exchange_bytes`` (each step's audited price) over
its counter ``rounds``, both over the process's run. Without faults this
is ``ShardedBackend.bytes_per_round``. Nothing is reported on a program
without either counter."""


def read(run):
    try:
        from repro import obs
    except ImportError:
        return None
    counters = obs.counters()
    if not counters.get("rounds") or "exchange_bytes" not in counters:
        return None
    return counters["exchange_bytes"] / counters["rounds"]
