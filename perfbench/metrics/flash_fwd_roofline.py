"""Share of its roofline that the flash-attention forward kernel reaches:
the least time of its calls (the larger of FLOPs over the bf16 peak and
bytes over HBM bandwidth, ``benchlib.flops``) over the device time of
their trace events, summed over the chips.

The kernel's ``pallas_call`` carries no name, so its calls are found as
Mosaic custom calls that take three operands of the output's shape
``bf16[batch*heads, seq, head_dim]`` (q, k and v); each call's work is
computed from that shape."""
import re

from benchlib.flops import flash_fwd_cost, roofline_seconds
from benchlib.trace import matching

CALL = re.compile(r"= (bf16\[(\d+),(\d+),(\d+)\])\{[^}]*\} custom-call\("
                  r"(.*)custom_call_target=\"tpu_custom_call\"")


def _shape(text):
    """(rows, seq, head_dim) of a flash forward call, else None."""
    m = CALL.search(text)
    if not m or m.group(5).count(m.group(1)) != 3:
        return None
    return int(m.group(2)), int(m.group(3)), int(m.group(4))


def read(run):
    if run.trace is None:
        return None
    least = seconds = 0.0
    for ops in matching(run.trace, r"tpu_custom_call").values():
        for o in ops:
            shape = _shape(o.text)
            if shape is None:
                continue
            rows, seq, hd = shape
            flops, bytes_ = flash_fwd_cost(rows, seq, hd)
            least += roofline_seconds(flops, bytes_, run.peaks.bf16_flops,
                                      run.peaks.hbm_bytes_per_s)[0]
            seconds += (o.end - o.start) * 1e-9
    if seconds <= 0:
        return None
    return least / seconds * 100.0
