"""Device milliseconds per event in the client stage program (gradient,
SAMomentum and selection, vmapped over the batch; jitted as ``run``)."""
from bench.trace_reduce import module_seconds

PROGRAMS = {"jit_run"}


def read(rec):
    s = rec["trace"] and module_seconds(rec["trace"], PROGRAMS)
    return 1e3 * s / rec["work"] if s else None
