"""The wait between the two: granted -> admitted. A slot, the pages and
the admission cap were settled (`granted_at`), and the request's prefill
program has been enqueued on the device (`admitted_at`): host prep, the
program lookup and the enqueue itself, which is where the runtime holds
the host back while the device's queue is full (flight recorder stamps,
95th percentile over the requests due in the window)."""
from harness import readers, stats

NAME, UNIT, BETTER = "dispatch_hold_p95_ms", "ms", "lower"
LAYER, SOURCE, MOVES, LOOP = "engine loop", "program_span", "ttft_p95_ms", "open"


def read(run):
    holds = [rec["admitted_at"] - rec["granted_at"]
             for _, rec in readers.paired(run)
             if rec.get("granted_at") and rec.get("admitted_at")]
    return stats.percentile(holds, 95) * 1e3 if holds else None
