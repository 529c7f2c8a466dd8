"""Page pool: the allocator's used_pages over the usable pages, the peak of
ten samples a second inside the window. Cached prefix pages count as used:
they are what the pool holds."""
NAME, UNIT, BETTER = "pool_pages_peak_pct", "%", "lower"
LAYER, SOURCE, MOVES = "page pool", "program_counter", "out_tok_s"


def read(run):
    used = [n for t, n in run["pool"]["samples"]
            if run["t_open"] <= t < run["t_close"]]
    return 100.0 * max(used) / run["pool"]["usable"] if used else None
