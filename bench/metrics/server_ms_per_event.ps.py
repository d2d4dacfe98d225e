"""Device milliseconds per event in the server stage program (receive
and secondary selection, scanned over the batch)."""
from bench.trace_reduce import module_seconds

PROGRAMS = {"jit_server_batch"}


def read(rec):
    s = rec["trace"] and module_seconds(rec["trace"], PROGRAMS)
    return 1e3 * s / rec["work"] if s else None
