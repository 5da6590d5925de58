"""prefill_mfu: the prompts' FLOPs (counts.prefill) over the prefill
tasks' execution time times the chip's peak, for the window's prefills."""
import counts
import peaks


def read(cell):
    flops = secs = 0.0
    for s, r in zip(cell.served, cell.prefill_records):
        if r.started:
            flops += counts.prefill(cell.cfg, s.prompt_len)[0]
            secs += r.t_end - r.t_start
    if not secs:
        return None
    return 100.0 * flops / (secs * peaks.peaks(cell.device_kind)["bf16_flops"])
