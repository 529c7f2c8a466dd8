"""What a prompt's program was enqueued behind: the decode steps the
device's queue held at that moment (a block's steps an entry of the
engine's deque, d + 1 a verify), as the engine counted them on the
request (`ahead_steps`), mean over the requests due in the window."""
from harness import readers

NAME, UNIT, BETTER = "prefill_ahead_steps_mean", "steps", "lower"
LAYER, SOURCE, MOVES, LOOP = "step programs", "program_span", "ttft_p95_ms", "open"


def read(run):
    ahead = [rec["ahead_steps"] for _, rec in readers.paired(run)
             if rec.get("ahead_steps") is not None]
    return sum(ahead) / len(ahead) if ahead else None
