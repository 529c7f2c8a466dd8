"""The share of a request's decode row-steps computed after its last
token: what was left of the block it finished in and the blocks already
queued behind it (`overrun_steps`, counted by the engine at the finish),
over those and the steps that gave it a token (every token but the first,
which is the prefill's), summed over the window's finished requests."""
from harness import readers

NAME, UNIT, BETTER = "decode_overrun_share_pct", "%", "lower"
LAYER, SOURCE, MOVES = "engine loop", "program_span", "out_tok_s"


def read(run):
    done = [rec for _, rec in readers.paired(run)
            if rec.get("finished_at") and rec.get("overrun_steps") is not None]
    overrun = sum(rec["overrun_steps"] for rec in done)
    steps = overrun + sum(max(0, rec["generated"] - 1) for rec in done)
    return 100.0 * overrun / steps if steps else None
