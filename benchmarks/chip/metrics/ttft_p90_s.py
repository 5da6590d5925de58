"""ttft_p90_s: time to first token from each request's due time, 90th
percentile over every request sent in the window; a request that never
gets its first token counts as infinitely late."""
import math

from harness import percentile


def read(cell):
    xs = [s.stamps[0] - s.due_t if s.stamps else math.inf
          for s in cell.served]
    return percentile(xs, 90) if xs else None
