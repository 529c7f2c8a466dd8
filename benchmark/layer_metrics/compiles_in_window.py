"""Programs the executor compiled inside the window: compile_table()'s
distinct programs at the close minus at the open. Expected 0; a run that
compiles in its window is refused by the harness."""
NAME, UNIT, BETTER = "compiles_in_window", "count", "lower"
LAYER, SOURCE, MOVES = "step programs", "program_counter", "out_tok_s"


def read(run):
    return float(run["close_table"]["distinct_programs"]
                 - run["setup_table"]["distinct_programs"])
