"""device_idle_share.chat: 1 - (union of device operation intervals / the
traced window), from the profiler trace."""
import trace


def read(cell):
    tr = cell.trace_data
    if tr is None or not tr["devices"]:
        return None
    return 100.0 * (1.0 - trace.busy_s(tr) / trace.window_s(tr))
