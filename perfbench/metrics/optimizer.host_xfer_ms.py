"""Per step, the device time spent waiting on copies between host memory
and HBM (``copy-done`` of a buffer in the host memory space, ``S(5)``):
the offloaded optimizer state's transfers that compute did not hide.
Worst chip, in ms."""
from benchlib.trace import matching, op_seconds


def read(run):
    if run.trace is None:
        return None
    waits = [op_seconds([o for o in ops if o.opcode == "copy-done"])
             for ops in matching(run.trace, r"S\(5\)").values()]
    if not any(waits):
        return None
    return max(waits) / run.steps * 1e3
