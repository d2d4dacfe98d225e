"""Share of the HBM roofline reached by the server commit and the worker
apply: the bytes their updates need (``bench.flops.commit_apply_bytes``,
from the message shapes) at the chip's peak bandwidth, over the device
time of the two programs."""
from bench.trace_reduce import module_seconds

PROGRAMS = {"jit_commit", "jit_apply_rows"}


def read(rec):
    s = rec["trace"] and module_seconds(rec["trace"], PROGRAMS)
    if not s or not rec.get("commit_apply_bytes"):
        return None
    need = rec["commit_apply_bytes"] / rec["peaks"]["hbm_bytes_per_s"]
    return 100.0 * need / s
