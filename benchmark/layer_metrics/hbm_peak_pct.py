"""Peak device memory over its limit, on the fullest chip:
memory_stats() peak_bytes_in_use / bytes_limit after the window."""
NAME, UNIT, BETTER = "hbm_peak_pct", "%", "lower"
LAYER, SOURCE, MOVES = "device", "program_counter", "out_tok_s"


def read(run):
    memory = run["memory"]
    return 100.0 * memory["peak"] / memory["limit"] if memory["limit"] else None
