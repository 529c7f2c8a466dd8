"""The engine loop's own time per decode step: over the window's
decode-phase ledger records, (wall - device_sync) / the decode steps those
records synced (tokens emitted / rows live, a block of 16 at most)."""
from harness import readers

NAME, UNIT, BETTER = "loop_host_ms_per_step", "ms", "lower"
LAYER, SOURCE, MOVES = "engine loop", "program_span", "out_tok_s"


def read(run):
    host = steps = 0.0
    for s in readers.decode_steps(run):
        if s["active_slots"] and s["tokens"]:
            host += s["wall_s"] - s["segments"].get("device_sync", 0.0)
            steps += s["tokens"] / s["active_slots"]
    return host / steps * 1e3 if steps else None
