"""How long a decode block is: the steps of the decode blocks the loop
enqueued in the window, over those blocks. The loop runs blocks of
`decode_block_size` steps or of half of it (`engine._decode_block_now`),
and a prompt waits out the block that runs when it arrives and the one
queued behind that. From the step records: `dispatches` holds a record's
decode blocks (`decode`) and, where the program counts them, the steps
they run together (`decode_steps`); `harness/serve.py` `step_rows` copies
`dispatches` whole, and a field of the record it does not name never
reaches a reader."""

NAME, UNIT, BETTER = "decode_block_steps_mean", "steps", "lower"
LAYER, SOURCE, MOVES, LOOP = "engine loop", "program_counter", "ttft_p95_ms", "open"


def read(run):
    blocks = steps = 0
    for row in run["steps"]:
        enqueued = row["dispatches"]
        if "decode_steps" in enqueued:
            blocks += enqueued["decode"]
            steps += enqueued["decode_steps"]
    return steps / blocks if blocks else None
