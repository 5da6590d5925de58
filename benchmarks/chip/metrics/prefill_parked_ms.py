"""prefill_parked_ms: mean wait of the window's prefill tasks in the
scheduler, from submission to the admission that handed them to the
execution pool (the executor's records: t_admit - t_queue). The rest of
``prefill_queue_ms`` is the wait for a pool worker."""


def read(cell):
    xs = [r.t_admit - r.t_queue for r in cell.prefill_records
          if r is not None and r.started and getattr(r, "t_admit", -1.0) >= 0]
    return sum(xs) / len(xs) * 1e3 if xs else None
