"""prefill_queue_ms: mean wait of the window's prefill tasks from
submission to the start of execution, from the executor's records."""


def read(cell):
    xs = [r.t_start - r.t_queue for r in cell.prefill_records if r.started]
    return sum(xs) / len(xs) * 1e3 if xs else None
