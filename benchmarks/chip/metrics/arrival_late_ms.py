"""arrival_late_ms: how late the harness submitted each request after its
due time (mean over the window), on its own clock."""


def read(cell):
    xs = [s.submit_t - s.due_t for s in cell.served]
    return sum(xs) / len(xs) * 1e3 if xs else None
