"""decode_chat_roofline: the decode steps' least time on the chip (the
larger of their FLOPs over peak and their bytes over HBM bandwidth,
counted by counts.decode at the rows and positions each step ran) over
the decode program's device time, in the traced part of the window."""
import counts
import peaks
import trace

from harness import DECODE_MODULE


def read(cell):
    tr = cell.trace_data
    if tr is None:
        return None
    dev_s, n = trace.module_s(tr, DECODE_MODULE)
    lo, hi = cell.trace_t
    steps = [p for p in cell.pumps if lo <= p[1] <= hi]
    if not dev_s or not steps:
        return None
    # the traced steps' mean least time, over the mean device time per step
    least = sum(peaks.least_time(*counts.decode(cell.cfg, rows, kv),
                                 cell.device_kind)
                for _, _, rows, kv in steps) / len(steps)
    return 100.0 * least / (dev_s / n)
