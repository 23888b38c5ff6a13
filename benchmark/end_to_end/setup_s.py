"""Process start to window start, seconds: native build if stale, input
generation, JAX and TPU initialisation, warm-up with compilation or the
load from the compile cache."""


def read(record: dict):
    return record["setup_s"]
