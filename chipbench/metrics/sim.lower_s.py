"""sim.lower_s: simcore's host lowering of a call's clusters to the
scan's inputs (LAST_PHASES["run:<policy>/lower"]), mean per call in the
window."""
from chipbench.spans import run_child_mean


def read(record):
    return run_child_mean(record, "lower")
