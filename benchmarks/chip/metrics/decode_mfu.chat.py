"""decode_mfu.chat: the whole decode step's share of the chip's peak:
the FLOPs of each step at the rows it ran over the time from the previous
step's end to its own, for steps that follow a step directly."""
import counts
import peaks


def read(cell):
    flops = secs = 0.0
    prev = None
    for t0, t1, rows, kv in cell.pumps:
        if prev is not None and t0 - prev < 0.05:
            flops += counts.decode(cell.cfg, rows, kv)[0]
            secs += t1 - prev
        prev = t1
    if not secs:
        return None
    return 100.0 * flops / (secs * peaks.peaks(cell.device_kind)["bf16_flops"])
