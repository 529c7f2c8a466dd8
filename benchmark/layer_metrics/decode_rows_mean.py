"""Batch occupancy: the step ledger's active_slots, mean over the window's
decode-phase records."""
from harness import readers

NAME, UNIT, BETTER = "decode_rows_mean", "rows", "higher"
LAYER, SOURCE, MOVES = "admission", "program_counter", "out_tok_s"


def read(run):
    rows = [s["active_slots"] for s in readers.decode_steps(run)]
    return sum(rows) / len(rows) if rows else None
