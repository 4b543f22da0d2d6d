"""sim.summarize_s: simcore's host summary of the scan's outputs
(LAST_PHASES["run:<policy>/summarize"]), mean per call in the window."""
from chipbench.spans import run_child_mean


def read(record):
    return run_child_mean(record, "summarize")
