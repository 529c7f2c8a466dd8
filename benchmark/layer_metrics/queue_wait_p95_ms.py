"""Admission's queue: flight recorder enqueued -> admitted, 95th
percentile over the requests due in the window."""
from harness import readers, stats

NAME, UNIT, BETTER = "queue_wait_p95_ms", "ms", "lower"
LAYER, SOURCE, MOVES, LOOP = "admission", "program_span", "ttft_p95_ms", "open"


def read(run):
    waits = [rec["admitted_at"] - rec["enqueued_at"]
             for _, rec in readers.paired(run) if rec.get("admitted_at")]
    return stats.percentile(waits, 95) * 1e3 if waits else None
