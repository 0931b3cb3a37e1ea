"""Seconds from the start of the process to the start of the window:
imports, device discovery, weights, server build, compile (or
compile-cache reads) and warm-up.  The reference's threshold profiling
is the benchmark's own work and is left out."""


def read(w):
    return w["setup_s"]
