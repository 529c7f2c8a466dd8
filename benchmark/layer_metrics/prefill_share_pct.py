"""How much of the loop's time goes to prompts: the share of the window's
ledger wall time in prefill-phase records."""
NAME, UNIT, BETTER = "prefill_share_pct", "%", "lower"
LAYER, SOURCE, MOVES = "engine loop", "program_span", "out_tok_s"


def read(run):
    total = sum(s["wall_s"] for s in run["steps"])
    prefill = sum(s["wall_s"] for s in run["steps"] if s["phase"] == "prefill")
    return 100.0 * prefill / total if total else None
