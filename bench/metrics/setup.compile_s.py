"""Set-up: backend compile seconds before the window, from JAX's
monitoring events (a persistent-cache retrieval counts as its compile)."""


def read(run):
    return run.compile_s_setup
