"""sim.transfer_s: the compiled pass's copies between host and device,
the inputs to the device until they are there and the outputs back
(LAST_PHASES["run:<policy>/put"] + [".../fetch"]), mean per call in the
window."""
from chipbench.spans import run_child_mean


def read(record):
    return run_child_mean(record, "put", "fetch")
