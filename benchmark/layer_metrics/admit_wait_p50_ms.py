"""Closed cells: how long a client's next request waits for its slot,
flight recorder enqueued -> admitted, median. Time in which that client
receives no token."""
from harness import readers, stats

NAME, UNIT, BETTER = "admit_wait_p50_ms", "ms", "lower"
LAYER, SOURCE, MOVES, LOOP = "admission", "program_span", "out_tok_s", "closed"


def read(run):
    waits = [rec["admitted_at"] - rec["enqueued_at"]
             for _, rec in readers.paired(run) if rec.get("admitted_at")]
    return stats.percentile(waits, 50) * 1e3 if waits else None
