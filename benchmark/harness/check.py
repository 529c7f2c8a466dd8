"""What decides `correct`: the served tokens judged by their margin under
the plain reference, and the state they were served from held to the
precision the configuration states. No token id is ever compared with a
token id.

Once the window has closed, a sample of the requests it finished, drawn
from the seed, is run through the reference, one forward pass over each
prompt with its served tokens: the longest and a few more over all their
tokens, and one request from every slot the engine filled over its first
tokens, so that a fault tied to one slot, page range or admission lands in
the sample. At every served position gap = (the reference's largest
logit) - (the reference's logit of the token that was served). A correct
engine whose logits are off by eps can never serve a token with gap > 2
eps, whatever the ties and whatever batch it ran in; a wrong page, slot,
table or hand-off serves tokens whose gap is of the order of the logit
scale. Three numbers are compared, each with its own limit from the cell's
file: `gap_max`, the widest gap; `gap_mean`, the mean gap over all checked
tokens, which grows with the square of the logit error and so separates
lower-precision arithmetic; and `state_not_as_stated`, the count of arrays
the engine serves from (page pools, weight matrices) that are not held in
the configuration's `precision`, an exact comparison. The last is there
because an int8 page pool with a scale a token and head keeps 7 bits
against bfloat16's 8 and attention averages its error over the context: no
statistic of served tokens separates it (PERF.md section 2 has the
readings).

The control (harness flag --control, never in the driver's runs): the same
positions, the token that the reference puts first when computed with int8
matrices, its gap under the float32 reference.
"""

import random

import jax
import jax.numpy as jnp
import numpy as np

from . import stats, traffic


def pick(records, slots: dict, seed: int, sample: dict):
    """[(record, served tokens to check)]: `full` requests over all their
    tokens, the longest first among them, then one request from each of up
    to `per_slot` distinct slots over its first `slot_tokens` tokens.
    `slots` maps a request's index to the slot that served it."""
    finished = sorted((r for r in records
                       if stats.answered(r) and len(r["tokens"]) >= 2),
                      key=lambda r: r["index"])
    if not finished:
        return []
    longest = max(finished,
                  key=lambda r: (r["prompt_tokens"] + len(r["tokens"]),
                                 -r["index"]))
    rest = [r for r in finished if r is not longest]
    random.Random(f"{seed}:check").shuffle(rest)
    n_full = max(0, int(sample["full"]) - 1)
    out = [(r, len(r["tokens"])) for r in [longest] + rest[:n_full]]
    by_slot = {}
    for rec in rest[n_full:]:
        slot = slots.get(rec["index"])
        if slot is not None:
            by_slot.setdefault(slot, rec)
    seen = sorted(by_slot)
    random.Random(f"{seed}:slots").shuffle(seen)
    first = int(sample.get("slot_tokens", 0))
    return out + [(by_slot[s], min(first, len(by_slot[s]["tokens"])))
                  for s in seen[:int(sample.get("per_slot", 0))]]


def sequence(rec: dict, n_check: int, seed: int, vocab: int):
    """(tokens, n_prompt, n_served): the prompt with its first n_check
    served tokens. A served special id cannot be read back from the SSE
    text (it renders empty), so the sequence stops before the first one."""
    prompt = traffic.prompt_ids(seed, rec["index"], rec["prompt_tokens"], vocab)
    served = rec["tokens"][:n_check]
    if -1 in served:
        served = served[:served.index(-1)]
    return prompt + served, len(prompt), len(served)


def padded_length(n: int, longest: int) -> int:
    """A handful of lengths, so that the reference compiles a handful of
    programs: the next power of two from 128, at most the cell's longest."""
    size = 128
    while size < n:
        size *= 2
    return min(size, longest)


@jax.jit
def _margins(logits, chosen):
    best = jnp.max(logits, axis=-1)
    got = jnp.take_along_axis(logits, chosen[:, None], axis=-1)[:, 0]
    return best - got, jnp.argmax(logits, axis=-1), jnp.max(jnp.abs(logits))


def gaps(reference, params, dims, tokens, n_prompt: int, n_served: int,
         pad_to: int, control=None):
    """The gaps at the n_served served positions of one sequence, and the
    control's at the same positions when asked for."""
    padded = list(tokens) + [0] * (pad_to - len(tokens))
    following = jnp.asarray(padded[1:] + [0], jnp.int32)
    logits = reference.logits(params, dims, padded)
    gap, _, scale = _margins(logits, following)
    rows = slice(n_prompt - 1, n_prompt - 1 + n_served)
    out = {"gap": np.asarray(gap)[rows].tolist(), "scale": float(scale)}
    if control:
        lower = reference.logits(params, dims, padded, lower=control)
        first = jnp.argmax(lower, axis=-1).astype(jnp.int32)
        out["control_gap"] = np.asarray(
            _margins(logits, first)[0])[rows].tolist()
    return out


def summarize(all_gaps) -> dict:
    flat = [g for seq in all_gaps for g in seq]
    return {"gap_max": max(flat), "gap_mean": sum(flat) / len(flat),
            "tokens": len(flat), "served_not_first":
            sum(1 for g in flat if g > 0.0)}


def held(engine) -> dict:
    """What the engine serves from, read while it is alive: every page
    pool and every weight array, with its dtype and bytes."""
    def facts(array):
        return {"dtype": str(array.dtype), "shape": list(array.shape),
                "nbytes": int(array.size) * array.dtype.itemsize}

    pools = {name: facts(getattr(engine, name))
             for name in ("k_cache", "v_cache", "k_scale", "v_scale")
             if getattr(engine, name, None) is not None}
    leaves = jax.tree_util.tree_leaves_with_path(engine.params)
    return {"pools": pools, "pool_tokens": (engine.allocator.n_pages
                                            * engine.page_size),
            "weights": {jax.tree_util.keystr(path): facts(leaf)
                        for path, leaf in leaves}}


def not_as_stated(state: dict, precision: str, dims: dict) -> list:
    """The arrays of `held` that are not in the stated precision: a pool or
    a weight matrix of another dtype, a pool of scales (the stated
    precision needs none), K and V together under the bytes a token that
    the precision takes."""
    width = jnp.dtype(precision).itemsize
    faults = [f"pool {name} is {pool['dtype']}" if name in ("k_cache", "v_cache")
              else f"pool {name} holds scales"
              for name, pool in sorted(state["pools"].items())
              if name not in ("k_cache", "v_cache")
              or pool["dtype"] != precision]
    need = 2 * dims["L"] * dims["Hkv"] * dims["dh"] * width
    have = sum(pool["nbytes"] for name, pool in state["pools"].items()
               if name in ("k_cache", "v_cache")) / state["pool_tokens"]
    if have < need:
        faults.append(f"K and V take {have:.0f} bytes a token, {precision} "
                      f"takes {need}")
    faults += [f"weight {name} is {leaf['dtype']}"
               for name, leaf in sorted(state["weights"].items())
               if len(leaf["shape"]) >= 2 and leaf["dtype"] != precision]
    return faults


def judge(reference, params, dims, run: dict, seed: int, check: dict,
          precision: str, pad_to: int, control=None) -> dict:
    """{"correct", "reasons", "numbers": {name: {"value", "limit"}}, ...}."""
    sample = pick(run["result"]["records"], run["slots"], seed,
                  check["sample"])
    if not sample:
        return {"correct": False, "reasons": ["no finished request to check"],
                "numbers": {}, "requests": 0}
    seqs = [sequence(r, n, seed, dims["V"]) for r, n in sample]
    seqs = [s for s in seqs if s[2] >= 1]
    results = [gaps(reference, params, dims, t, p, n,
                    padded_length(len(t), pad_to), control)
               for t, p, n in seqs]
    found = summarize([r["gap"] for r in results])
    faults = not_as_stated(run["held"], precision, dims)
    found["state_not_as_stated"] = len(faults)
    numbers = {name: {"value": found[name], "limit": check["limits"][name]}
               for name in ("gap_max", "gap_mean", "state_not_as_stated")}
    reasons = [f"{name} {n['value']:.6g} is over its limit {n['limit']:.6g}"
               for name, n in numbers.items() if not n["value"] <= n["limit"]]
    out = {"correct": not reasons, "reasons": reasons + faults[:8],
           "numbers": numbers, "requests": len(seqs),
           "slots": len({run["slots"].get(r["index"]) for r, _ in sample}
                        - {None}),
           "tokens": found["tokens"],
           "padded_to": {str(n): sum(1 for t, _, _ in seqs
                                     if padded_length(len(t), pad_to) == n)
                         for n in sorted({padded_length(len(t), pad_to)
                                          for t, _, _ in seqs})},
           "served_not_first": found["served_not_first"],
           "logit_scale": max(r["scale"] for r in results),
           "longest": max(len(t) for t, _, _ in seqs)}
    if control:
        ctl = summarize([r["control_gap"] for r in results])
        out["control"] = {"what": f"reference at {control}",
                          "gap_max": ctl["gap_max"],
                          "gap_mean": ctl["gap_mean"]}
    return out
