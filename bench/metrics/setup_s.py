"""Seconds from the start of the run to the opening of traffic: imports,
traffic generation, weights, engine construction and warm-up compiles
(or compile-cache loads)."""


def read(run):
    return run.setup_s
