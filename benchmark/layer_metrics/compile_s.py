"""Seconds the executor spent compiling or loading the cell's programs
during set-up (compile_table() at the window's open)."""
NAME, UNIT, BETTER = "compile_s", "s", "lower"
LAYER, SOURCE, MOVES = "step programs", "program_counter", "setup_s"


def read(run):
    return float(run["setup_table"]["compile_seconds_total"])
