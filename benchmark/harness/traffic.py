"""The one general traffic generator. Standard library only: the load
generator's child process imports it and must never import JAX.

A traffic mix is a data file of parameters (benchmark/traffic/<mix>.json).
Every seed gets the SAME multiset of sizes and arrival gaps, in another
order: sizes are the quantiles of the mix's distributions on a grid of
`grid` points, and request i takes point perm_b[i % grid] of block
b = i // grid, where perm_b is a permutation drawn from the seed. So two
seeds do the same work and differ only in who sends what when.
"""

import math
import random
import statistics

# DebugTokenizer's specials (gofr_tpu/models/tokenizer.py), ids 256-258,
# decode to no character: prompts avoid them and a served one cannot be
# read back
FIRST_PLAIN_ID = 259
_PUA = 0xE000


def quantile(spec: dict, u: float) -> int:
    """The u-quantile of a length distribution, as a whole number of tokens."""
    if spec["dist"] == "uniform":
        value = spec["lo"] + u * (spec["hi"] - spec["lo"])
    elif spec["dist"] == "lognormal":
        value = math.exp(math.log(spec["median"])
                         + spec["sigma"] * statistics.NormalDist().inv_cdf(u))
    elif spec["dist"] == "fixed":
        value = spec["value"]
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    lo, hi = spec.get("lo", 1), spec.get("hi", value)
    return int(round(min(max(value, lo), hi)))


def upper(spec: dict) -> int:
    """The longest length a distribution can give."""
    return int(spec["hi"] if "hi" in spec else spec["value"])


def grid_points(n: int):
    return [(i + 0.5) / n for i in range(n)]


def _rng(seed: int, *what) -> random.Random:
    return random.Random(":".join(str(w) for w in (seed,) + what))


class Schedule:
    """request(i) for i = 0, 1, 2, ...: sizes and, in an open loop, the gap
    to the previous arrival. Unbounded, so a fast system never runs dry."""

    def __init__(self, mix: dict, seed: int):
        self.mix, self.seed = mix, int(seed)
        self.grid = int(mix.get("grid", 64))
        us = grid_points(self.grid)
        self._prompts = [quantile(mix["prompt_tokens"], u) for u in us]
        self._outputs = [quantile(mix["output_tokens"], u) for u in us]
        rate = float(mix.get("rate_rps", 0.0))
        self._gaps = ([-math.log(1.0 - u) / rate for u in us]
                      if mix["loop"] == "open" else [0.0] * self.grid)
        if mix["loop"] == "open" and mix.get("arrivals", "poisson") != "poisson":
            raise ValueError(f"unknown arrivals {mix['arrivals']!r}")
        self._perms = {}

    def _perm(self, block: int):
        perms = self._perms.get(block)
        if perms is None:
            perms = []
            for what in ("prompt", "output", "gap"):
                order = list(range(self.grid))
                _rng(self.seed, what, block).shuffle(order)
                perms.append(order)
            self._perms[block] = perms
        return perms

    def request(self, i: int) -> dict:
        block, j = divmod(i, self.grid)
        p, o, g = self._perm(block)
        return {"index": i, "prompt_tokens": self._prompts[p[j]],
                "output_tokens": self._outputs[o[j]], "gap_s": self._gaps[g[j]]}

    def first_outputs(self, clients: int):
        """Closed loop: each client's first request is cut to a length drawn
        uniformly from 1 to its own, so that finishing times are de-phased.
        The cuts are the quantile grid over the clients, permuted."""
        us = grid_points(clients)
        _rng(self.seed, "dephase").shuffle(us)
        return [max(1, int(math.ceil(us[c] * self.request(c)["output_tokens"])))
                for c in range(clients)]


def prompt_ids(seed: int, index: int, n_tokens: int, vocab: int):
    """The prompt of request `index`, BOS included: n_tokens ids. Unique
    random ids, so no two prompts share a page and the prefix cache never
    hits. The server's tokenizer adds the BOS itself, so the text carries
    n_tokens - 1 characters."""
    rng = _rng(seed, "prompt", index)
    return [257] + [rng.randrange(FIRST_PLAIN_ID, vocab)
                    for _ in range(n_tokens - 1)]


def ids_to_text(ids) -> str:
    """DebugTokenizer's own mapping for ids >= 259: one private-use
    character each. BOS is left to the server."""
    return "".join(chr(_PUA + i) for i in ids if i >= FIRST_PLAIN_ID)


def _byte_table():
    """GPT-2's printable stand-ins for the 256 byte values, which
    DebugTokenizer uses for ids 0..255 (written out from the published
    bytes_to_unicode, not imported from the program)."""
    keep = (list(range(ord("!"), ord("~") + 1))
            + list(range(0xA1, 0xAD)) + list(range(0xAE, 0x100)))
    table, extra = {}, 0
    for b in range(256):
        if b in keep:
            table[chr(b)] = b
        else:
            table[chr(256 + extra)] = b
            extra += 1
    return table


_BYTE_CHARS = _byte_table()


def char_to_id(ch: str) -> int:
    """One SSE token event back to its id: one character per plain token;
    an empty event is a special id that cannot be told apart (-1)."""
    if not ch:
        return -1
    if len(ch) != 1:
        raise ValueError(f"a token event carried {len(ch)} characters")
    code = ord(ch)
    if code >= _PUA + FIRST_PLAIN_ID:
        return code - _PUA
    return _BYTE_CHARS[ch]
