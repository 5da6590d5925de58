"""itl_p95_ms: 95th percentile of every gap between consecutive output
tokens of every request sent in the window."""
from harness import percentile


def read(cell):
    gaps = [b - a for s in cell.served for a, b in zip(s.stamps, s.stamps[1:])]
    return percentile(gaps, 95) * 1e3 if gaps else None
