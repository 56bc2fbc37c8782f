"""Per step, the device time of collective operations, hidden or not:
the union of their intervals (selected by opcode, their ``-start`` and
``-done`` halves included), worst chip, in ms.  Read beside
``collective.exposed_ms``, it says whether collectives got shorter or
only better hidden."""
from benchlib.trace import COLLECTIVE, union


def read(run):
    if run.trace is None:
        return None
    worst = None
    for ops in run.trace.devices.values():
        coll = union([(o.start, o.end) for o in ops
                      if COLLECTIVE.match(o.opcode)])
        if coll:
            ns = sum(e - s for s, e in coll)
            worst = ns if worst is None else max(worst, ns)
    return None if worst is None else worst * 1e-9 / run.steps * 1e3
