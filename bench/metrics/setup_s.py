"""Seconds from the start of the process to the first timed round or step:
loading, data, weights, compiling or loading the programs, warming them."""


def read(rec):
    return rec.setup_s
