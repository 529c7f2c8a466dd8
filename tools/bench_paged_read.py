"""The paged kernels alone, at the benchmark cells' shapes, on the chip.

    chiprun -- python tools/bench_paged_read.py [only=part,...] [label=path/to/paged_attention.py ...]

Times `paged_attention` of this tree, and of any other copy of the module
given as label=path (a parent's unpacked under build/, an experiment), over
the stacked pool of `internlm2-1.8b`'s cells (24 layers x 769 pages of 128
tokens, 96 rows, a table 16 wide) under two tables: `closed` (93 live rows
of about 480 tokens, as `decode-closed` holds) and `chat` (50 live rows and
46 of one page, as `chat-open` held before PR 25). One JSON line a
(kernel, pool dtype, table): microseconds a call (best of 5 runs of a
24-layer loop x 8), the share of the device's peak bytes/s over the WHOLE
live pages, and the largest error against `paged_attention_reference` on
one layer.

The width of a fold (PR 32: `pages_per_fold`, the pages one turn of the
read's loop folds) is the module's own rule unless a line says otherwise:
the tool times other widths by standing in for the rule in the module it
loaded, which nothing that serves can do.

Then the decode block's tail (PR 28), for every module that has one, at
two geometries (`internlm2`: 8 KV heads x 2 queries, 24 layers; `nemotron`:
2 KV heads x 16 queries, 2 layers of attention) under the `closed` table,
block 16: `read+tail` (`paged_attention_in_block`: the step's token put
into the tail and the read over pages and tail, a call, the mean over the
block's 16 steps), `tail_put` (the put as a plain window update alone: the
reference, not the serving path), `flush` (one flush of all layers, a
layer) and, for comparison, `write` (the per-token page write a call,
which the int8 pools and the verify window still run). `read+tail` is
checked against the reference on a layer that had the tail's tokens
written column by column, `flush` against those columns, exactly. The
read is timed at folds of one page as well, at `nemotron` also of 4.

Then the latent read (PR 32: `mla_read`, the same kernel body on one pool)
at the geometry of `joyai-llm-flash-ep8.longprompt-closed`: 12 layers x
4,600 pages of 1 x 576 x 128, 128 rows of 32 queries, a table 64 wide, 122
live rows of 2,560-5,120 tokens, block 16; microseconds a call (the mean
over the block's 16 steps x 12 layers) at folds of 1, 2, 4, 8 and 16 pages
and at the rule's own, the share of the device's peak bytes/s over the whole
live pages, and the largest error against `mla_read_reference` on a layer
that had the tail's tokens written column by column.

Then (PR 33) the two reads of `trinity-large-preview-ep8.mixedlen-closed`,
8 KV heads x 6 queries of 128, 32 rows of which 31 live at contexts of
1,500-13,000 tokens, block 16: `window_read` over the window group (4
layers x 1,089 pages, a ring of 34 pages a row, the lower bound position -
4,095 a row) and `paged_read` over the full block beside it (1 layer x
2,300 pages, a table 128 wide); microseconds a call, the share of the
device's peak bytes/s over the tokens a row still sees, and the largest
error against masked attention over the same pages. And beside them the
two other kernels that cell added work to, alone: the tiled gated experts
at 3072 x 3072 (32 held, the 12 a step of 31 rows touches) and the prefill's
flash attention over 12,288 tokens of 48 / 8 heads, told the window and not.

Then (PR 40) the SHORT rows of `xing4.0-29b-a4b-ep8.decode-closed`: the
latent read at 20 layers x 865 pages, 96 rows of 32 queries under a table 16
wide, 93 live rows at the lengths `decode-closed` holds (32-128 in, up to
512-1,024 out, a request caught anywhere in its life: 1-9 pages, nearly all
ONE fold of 8), block 16. A fold is computed as wide as the pages it copied
(`fold_branch`); beside the rule's own line the tool times folds of 1, 2 and
4 pages, the same rows with every fold computed at all C pages (`computed_
pages`: the program of a module from before PR 40, made in this one by
standing in for `fold_branch`), and tables whose live rows ALL hold n pages
(`pages_a_row` 1, 2, 4, 8), each computed at every width that covers n (the
kernel still chooses: its choice is stood in for, not taken out): what a
row costs by what it copies and by what it computes. And nemotron's
read+tail (2 KV heads x 16 queries, 2 layers) at the same rows.
Since PR 48 a grid step of the read walks R consecutive rows
(`rows_a_step`) and takes a one-fold row's tail and fold in one softmax
step: beside the rule's own line `short` times the cell's rows at R = 1 with
the tail's step apart (the walk of a module from before PR 48, made in this
one by standing in for the rule and for `_JOIN_ONE_FOLD`), at R = 1 with the
tail joined, at the rule's R with the tail's step apart, and at any other R
given as `groups=2,3`: what each part gave. `only=rows` is those lines alone
(under a minute a module: the first look at a change to the walk).
`only=` names the parts to run, in the order above: `pools`, `tail`,
`latent`, `short`, `trinity` (every module's two reads of that cell; its
experts and prefill attention beside the tree's).

It is not the benchmark: it says what a kernel costs alone, never what a
cell gains (PERF.md section 5 keeps its table). It refuses a device that
is not in the benchmark's table of peaks: a CPU timing of the interpreter
is no kernel time.
"""

import contextlib
import importlib.util
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "benchmark")]
import gofr_tpu.ops.paged_attention  # noqa: E402
from gofr_tpu.ops.mla_read import mla_read_reference  # noqa: E402
from harness import peaks  # noqa: E402  (the one table of peaks)

L, P, HKV, DH, PS, B, NP, H = 24, 769, 8, 128, 128, 96, 16, 16
STEPS = 8


def load(label: str, path: str):
    """Another copy of ops/paged_attention.py as a sibling module, so its
    relative imports resolve against this tree's package."""
    spec = importlib.util.spec_from_file_location(
        f"gofr_tpu.ops._bench_{label}", path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = module
    spec.loader.exec_module(module)
    return module


def table_of(name, rng, room: int = 0, n_pool_pages: int = P):
    """(table [B, NP], lengths [B], whole live pages). `room`: tokens each
    live row's pages must still take past its length (a decode block). A
    row of length 0 or 1 holds no request: its table is all page 0. `name`
    an int: every live row holds that many pages, the last one filled to
    anywhere."""
    rows, width = B, NP
    if isinstance(name, int):
        lengths = (name - 1) * PS + rng.integers(1, PS + 1 - room, size=B)
        lengths[[17, 40, 77]] = 0
    elif name == "short":
        # a slot of `decode-closed` at a random instant: a request (drawn
        # in proportion to how long it lasts) somewhere in its output
        prompts = rng.integers(32, 129, size=4 * B)
        outputs = rng.integers(512, 1025, size=4 * B)
        held = rng.choice(4 * B, size=B, replace=False,
                          p=outputs / outputs.sum())
        lengths = prompts[held] + (
            rng.random(B) * (outputs[held] - room)).astype(np.int64)
        lengths[[17, 40, 77]] = 0
    elif name == "closed":
        lengths = rng.integers(60, 900, size=B)
        lengths[5] = 1152
        lengths[[17, 40, 77]] = 1
    elif name == "longprompt":
        rows, width = LATENT["rows"], LATENT["table"]
        lengths = rng.integers(2560, 5120 - room, size=rows)
        lengths[[17, 40, 77, 90, 101, 127]] = 0
    else:
        lengths = np.ones(B, np.int64)
        live = rng.permutation(B)[:50]
        lengths[live] = rng.integers(40, 700, size=50)
        lengths[live[0]] = 1100
    n_pages = -(-lengths // PS)
    table = np.zeros((rows, width), np.int32)
    free = list(rng.permutation(np.arange(1, n_pool_pages)))
    for b in np.flatnonzero(lengths > 1):
        held = -(-(lengths[b] + room) // PS)
        table[b, :held] = [free.pop() for _ in range(held)]
    return (jnp.asarray(table), jnp.asarray(lengths, jnp.int32),
            int(n_pages.sum()))


def pools_of(dtype):
    """(k_pool, v_pool, scales): one random layer tiled over the stack."""
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
    stack = jax.jit(lambda x: jnp.tile(x[None], (L,) + (1,) * x.ndim))
    if dtype == jnp.int8:
        make = lambda k: stack(jax.random.randint(           # noqa: E731
            k, (P, HKV, DH, PS), -127, 128, jnp.int32).astype(jnp.int8))
        scales = [stack(jax.random.uniform(k, (P, HKV, PS), jnp.float32,
                                           0.005, 0.02))
                  for k in jax.random.split(k3)]
    else:
        make = lambda k: stack(jax.random.normal(            # noqa: E731
            k, (P, HKV, DH, PS), jnp.float32).astype(jnp.bfloat16))
        scales = []
    return make(k1), make(k2), scales


def best_of_five(fn, *args, donated=()) -> float:
    """Seconds a run, the best of five after one that compiles. `donated`:
    positions of arguments the run consumes and returns first."""
    def run(args):
        out = fn(*args)
        jax.block_until_ready(out)
        for i, x in zip(donated, out):
            args[i] = x
        return args

    args = run(list(args))
    best = float("inf")
    for _ in range(5):
        start = time.perf_counter()
        args = run(args)
        best = min(best, time.perf_counter() - start)
    return best


def time_one(module, args) -> float:
    """Microseconds a call, from the best of five 24-layer loops x STEPS."""
    def loop(q, *rest):
        def layer(l, acc):
            return acc + module.paged_attention(
                q, *rest, layer=l).astype(jnp.float32)

        return jax.lax.fori_loop(
            0, STEPS, lambda _, acc: jax.lax.fori_loop(0, L, layer, acc),
            jnp.zeros(q.shape, jnp.float32))

    return best_of_five(jax.jit(loop), *args) / (L * STEPS) * 1e6


# -- the decode block's tail ----------------------------------------------------
BLOCK = 16
GEOMETRIES = {"internlm2": (24, 769, 8, 16), "nemotron": (2, 961, 2, 32)}
# fold widths timed beside the rule's own (None), where the rule might
# have chosen otherwise
FOLDS = {"internlm2": (1, None), "nemotron": (1, 4, None)}
LATENT = {"name": "latent", "layers": 12, "pages": 4600, "width": 576,
          "value_width": 512, "rows": 128, "heads": 32, "table": 64,
          "scale": 192 ** -0.5, "folds": (1, 2, 4, 8, 16, None),
          "tables": ("longprompt",)}
# xing's cell: the same page, short rows (`table_of`: "short", and every live
# row of n pages)
SHORT = {"name": "short", "layers": 20, "pages": 865, "width": 576,
         "value_width": 512, "rows": B, "heads": 32, "table": NP,
         "scale": 192 ** -0.5, "folds": (1, 2, 4, None),
         "tables": ("short", 1, 2, 4, 8)}


@contextlib.contextmanager
def folding(module, pages, pools, width: int):
    """Inside, `module`'s reads fold `pages` pages a turn whatever its rule
    says (None: as the rule has it; a module from before PR 32 folds one).
    Yields what a read of `pools` under a table `width` wide then folds."""
    rule = getattr(module, "pages_per_fold", None)
    if rule is None:
        yield 1
        return
    if pages:
        module.pages_per_fold = lambda *_: pages
    try:
        yield pages or module.fold_of(pools, width)
    finally:
        module.pages_per_fold = rule


@contextlib.contextmanager
def computing(module, pages):
    """Inside, `module`'s reads compute a fold at least `pages` pages wide,
    whatever it copied (None, or a module from before PR 40: as the module
    has it): the module's own choice where that is wider, which it is for
    a row without a page (the kernel gives it a whole fold and runs no
    turn: a narrow turn there would wait for copies nobody started)."""
    rule = getattr(module, "fold_branch", None)
    if pages and rule is not None:
        module.fold_branch = lambda live, fold: jnp.minimum(
            rule(live, fold), module.fold_widths(fold).index(pages))
    try:
        yield
    finally:
        if rule is not None:
            module.fold_branch = rule


@contextlib.contextmanager
def walking(module, rows, joined):
    """Inside, a grid step of `module`'s reads walks `rows` rows whatever
    its rule says, and a row of one fold takes its tail and its fold in one
    softmax step or not, as `joined` says (None: as the module has them; a
    module from before PR 48 walks a row a step and joins nothing). Yields
    (rows a step as the rule would have them for the call given, joined)
    through a function of the call's pools, table width and rows."""
    rule = getattr(module, "rows_a_step", None)
    if rule is None:
        yield lambda *_: (1, False)
        return
    was = module._JOIN_ONE_FOLD
    if rows:
        module.rows_a_step = lambda *_: rows
    if joined is not None:
        module._JOIN_ONE_FOLD = joined
    try:
        yield lambda pools, width, n: (module.group_of(pools, width, n),
                                       module._JOIN_ONE_FOLD)
    finally:
        module.rows_a_step, module._JOIN_ONE_FOLD = rule, was


def tail_lines(label: str, module, geometry: str, device,
               rows: str = "closed") -> None:
    """The block's tail at one geometry (layers, pages, KV heads, heads)
    under the table `rows` names: one JSON line a measurement (see the
    module docstring); under another table than `closed`, the reads only."""
    layers, pages, n_kv, heads = GEOMETRIES[geometry]
    keys = jax.random.split(jax.random.PRNGKey(2), 5)
    draw = lambda k, shape: jax.random.normal(           # noqa: E731
        k, shape, jnp.float32).astype(jnp.bfloat16)
    one = [draw(k, (pages, n_kv, DH, PS)) for k in keys[:2]]
    stack = jax.jit(lambda x: jnp.tile(x[None], (layers, 1, 1, 1, 1)))
    k_pool, v_pool = stack(one[0]), stack(one[1])
    q = draw(keys[2], (B, heads, DH))
    news = [draw(k, (BLOCK, B, n_kv, DH)) for k in keys[3:]]
    table, starts, live_pages = table_of(rows, np.random.default_rng(1),
                                         room=BLOCK, n_pool_pages=pages)
    live = table[:, 0] > 0
    paged = jnp.where(live, starts, 0)
    counts = jnp.where(live, BLOCK, 0)

    def line(what: str, us: float, **more) -> None:
        print(json.dumps({
            "device": device.device_kind, "kernel": label, "what": what,
            "geometry": geometry, "table": rows, "rows": int(live.sum()),
            "live_pages": live_pages, "block": BLOCK,
            "us": round(us, 1), **more}), flush=True)

    def filled(k_tail, v_tail, layer, tokens=BLOCK):
        for t in range(tokens):
            k_tail, v_tail = module.tail_put(k_tail, v_tail, news[0][t],
                                             news[1][t], layer, t)
        return k_tail, v_tail

    def steps(read: bool):
        """BLOCK steps x `layers` of the read that puts, or of the plain
        put alone."""
        def run(q, k_pool, v_pool):
            def layer(l, carry):
                t, acc, k_tail, v_tail = carry
                if read:
                    out, k_tail, v_tail = module.paged_attention_in_block(
                        q, news[0][t], news[1][t], k_pool, v_pool, k_tail,
                        v_tail, table, paged, jnp.where(live, t + 1, 0),
                        layer=l)
                    acc = acc + out.astype(jnp.float32)
                else:
                    k_tail, v_tail = module.tail_put(
                        k_tail, v_tail, news[0][t], news[1][t], l, t)
                return t, acc, k_tail, v_tail

            def step(t, carry):
                return jax.lax.fori_loop(0, layers, layer, (t,) + carry)[1:]

            acc, k_tail, v_tail = jax.lax.fori_loop(
                0, BLOCK, step, (jnp.zeros(q.shape, jnp.float32),
                                 *module.block_tail(k_pool, B, BLOCK)))
            return (acc + k_tail[0, :, 0, 0, :1][:, :, None]
                    + v_tail[0, :, 0, 0, :1][:, :, None])

        return (best_of_five(jax.jit(run), q, k_pool, v_pool)
                / (layers * BLOCK) * 1e6)

    # what one layer holds once the block's tokens are written by columns
    last = layers - 1
    want_k, want_v = one
    for t in range(BLOCK):
        want_k, want_v = (
            module._write_columns([x[None]], [new[t]], table, starts + t,
                                  0)[0][0]
            for x, new in ((want_k, news[0]), (want_v, news[1])))
    want = jax.jit(gofr_tpu.ops.paged_attention.paged_attention_reference)(
        q.astype(jnp.float32), want_k, want_v, table,
        jnp.where(live, starts + BLOCK, 0))
    def last_step(q, k_pool, v_pool):
        return module.paged_attention_in_block(
            q, news[0][-1], news[1][-1], k_pool, v_pool,
            *filled(*module.block_tail(k_pool, B, BLOCK), last, BLOCK - 1),
            table, paged, counts, layer=jnp.int32(last))[0]

    reads_only = rows != "closed"
    if not reads_only:
        line("tail_put", steps(read=False))
    for pages in FOLDS[geometry]:
        if pages and not hasattr(module, "pages_per_fold"):
            continue
        with folding(module, pages, (k_pool, v_pool), NP) as folded:
            # a new function a width: jit keeps its traces by function
            got = jax.jit(lambda *a: last_step(*a))(q, k_pool, v_pool)
            line("read+tail", steps(read=True), pages_per_fold=folded,
                 by_rule=pages is None, max_abs_err=float(np.max(np.abs(
                     np.asarray(got, np.float32) - np.asarray(want)))))
    if reads_only:
        return

    def write(k_pool, v_pool):
        def layer(l, pools):
            return module.paged_write_decode(
                *pools, news[0][0], news[1][0], table, starts, layer=l)

        return jax.lax.fori_loop(
            0, STEPS, lambda _, pools: jax.lax.fori_loop(
                0, layers, layer, pools), (k_pool, v_pool))

    seconds = best_of_five(jax.jit(write, donate_argnums=(0, 1)), k_pool,
                           v_pool, donated=(0, 1))
    line("write", seconds / (layers * STEPS) * 1e6)
    del k_pool, v_pool

    # the flush: tails that hold the block in every layer, pools consumed
    k_pool, v_pool = stack(one[0]), stack(one[1])
    tails = jax.jit(lambda k: tuple(
        jnp.tile(x[last][None], (layers, 1, 1, 1, 1))
        for x in filled(*module.block_tail(k, B, BLOCK), last)))(k_pool)

    def flush(k_pool, v_pool, k_tail, v_tail):
        return jax.lax.fori_loop(
            0, STEPS, lambda _, pools: module.paged_flush_block(
                *pools, k_tail, v_tail, table, starts, counts),
            (k_pool, v_pool))

    fn = jax.jit(flush, donate_argnums=(0, 1))
    pools = fn(k_pool, v_pool, *tails)
    exact = all(bool(jnp.array_equal(pool[last, 1:], want[1:]))
                for pool, want in zip(pools, (want_k, want_v)))
    seconds = best_of_five(fn, *pools, *tails, donated=(0, 1))
    line("flush", seconds / (layers * STEPS) * 1e6, equals_columns=exact)


def latent_lines(label: str, module, device, peak_bytes_s: float,
                 g: dict = LATENT, groups=(), rows_only=False) -> None:
    """`mla_read` at a latent geometry (`LATENT`: the `longprompt-closed`
    cell's; `SHORT`: xing's `decode-closed`), under each of its tables: one
    JSON line a fold width, and under a table of n pages a row one a width
    its folds are computed at (see the module docstring). The call is
    `mla_read`'s own (ops/mla_read.py), made on `module`'s `_paged_read`."""
    layers, rows, w, r = g["layers"], g["rows"], g["width"], g["value_width"]
    keys = jax.random.split(jax.random.PRNGKey(3), 3)
    draw = lambda k, shape: jax.random.normal(           # noqa: E731
        k, shape, jnp.float32).astype(jnp.bfloat16)
    one = draw(keys[0], (g["pages"], 1, w, PS))
    q = draw(keys[1], (rows, g["heads"], w))
    news = draw(keys[2], (BLOCK, rows, 1, w))
    last = layers - 1
    oracle = jax.jit(lambda *a: mla_read_reference(
        *a, value_width=r, scale=g["scale"]))

    def case(name):
        """A table, and what the reference reads of one layer once the
        block's tokens are written by columns (16 rows at a time: the
        oracle gathers every row's whole table in float32)."""
        table, starts, live_pages = table_of(
            name, np.random.default_rng(1), room=BLOCK,
            n_pool_pages=g["pages"])
        live = table[:, 0] > 0
        want_pool = one
        for t in range(BLOCK):
            want_pool = module._write_columns([want_pool[None]], [news[t]],
                                              table, starts + t, 0)[0][0]
        after = jnp.where(live, starts + BLOCK, 0)
        want = np.concatenate([np.asarray(oracle(
            q[i:i + 16].astype(jnp.float32), want_pool, table[i:i + 16],
            after[i:i + 16])) for i in range(0, rows, 16)])
        return name, table, starts, live, live_pages, want

    # before the stack is made: the written layer is a pool's worth itself
    cases = [case(name) for name in g["tables"][:1 if rows_only else None]]
    pool = jax.jit(lambda x: jnp.tile(x[None], (layers, 1, 1, 1, 1)))(one)
    rule = getattr(module, "fold_of", lambda *_: 1)((pool,), g["table"])

    for name, table, starts, live, live_pages, want in cases:
        floor_us = (live_pages * w * PS * one.dtype.itemsize / peak_bytes_s
                    * 1e6)

        def read(q, new, pool, tail, tail_lens, layer):
            return module._paged_read(
                q, [pool], table, starts, (new, tail, tail_lens), layer,
                None, None, value_width=r, scale=g["scale"],
                scope="mla_read")

        def run(q, pool):
            def layer(l, carry):
                t, acc, tail = carry
                out, tail = read(q, news[t], pool, tail,
                                 jnp.where(live, t + 1, 0), l)
                return t, acc + out.astype(jnp.float32), tail

            acc, tail = jax.lax.fori_loop(
                0, BLOCK, lambda t, carry: jax.lax.fori_loop(
                    0, layers, layer, (t,) + carry)[1:],
                (jnp.zeros((rows, g["heads"], r), jnp.float32),
                 module.plane_tail(pool, rows, BLOCK)))
            return acc + tail[0, :, 0, 0, :1][:, :, None]

        def last_step(q, pool):
            tail = module.plane_tail(pool, rows, BLOCK)
            for t in range(BLOCK - 1):
                tail = jax.lax.dynamic_update_slice(
                    tail, jnp.pad(news[t], ((0, 0), (0, 0), (
                        0, tail.shape[-1] - w)))[None, :, :, None],
                    (last, 0, 0, t, 0))
            return read(q, news[-1], pool, tail, jnp.where(live, BLOCK, 0),
                        jnp.int32(last))[0]

        # (pages a fold, pages a fold is computed at, rows a grid step,
        # whether a one-fold row's tail joins its fold's softmax step):
        # None = the module's own. A table of n pages a row: the rule's
        # fold as it is, then computed at every width that covers n; the
        # cell's rows: every fold width, then the rule's with nothing
        # narrowed, then (the short rows) the walk a row a step with the
        # tail's step apart (the walk of a module from before PR 48), each
        # of the two parts alone, and any other `groups=` asked for
        narrows = hasattr(module, "fold_branch")
        if isinstance(name, int):
            lines = [(None, None, None, None)]
            if narrows:
                lines += [(None, width, None, None) for width in reversed(
                    module.fold_widths(rule)) if width >= name]
        else:
            lines = [(pages, None, None, None) for pages in g["folds"]]
            if narrows and g is SHORT:
                lines.append((None, rule, None, None))
            if rows_only:
                lines = [(None, None, None, None)]
            if g is SHORT and hasattr(module, "rows_a_step"):
                lines += [(None, None, 1, False), (None, None, 1, True),
                          (None, None, None, False)]
                lines += [(None, None, rows, True) for rows in groups]
        for pages, computed, walked, joined in lines:
            if pages and not hasattr(module, "pages_per_fold"):
                continue
            with folding(module, pages, (pool,), g["table"]) as folded, \
                    computing(module, computed), \
                    walking(module, walked, joined) as walk:
                # a new function a width: jit keeps its traces by function
                got = jax.jit(lambda *a: last_step(*a))(q, pool)
                us = (best_of_five(jax.jit(lambda *a: run(*a)), q, pool)
                      / (layers * BLOCK) * 1e6)
                rows_a_step, tail_joined = walk((pool,), g["table"], rows)
            print(json.dumps({
                "device": device.device_kind, "kernel": label,
                "what": "mla_read", "geometry": g["name"],
                **({"pages_a_row": name} if isinstance(name, int)
                   else {"table": name}),
                "rows": int(live.sum()), "live_pages": live_pages,
                "block": BLOCK, "pages_per_fold": folded,
                "by_rule": pages is None,
                "computed_pages": computed or (
                    "copied" if narrows else folded),
                "rows_a_step": rows_a_step, "tail_joined": tail_joined,
                "by_rows_rule": walked is None and joined is None,
                "us": round(us, 1),
                "us_a_row": round(us / int(live.sum()), 3),
                "whole_pages_share_of_peak_pct": round(
                    100 * floor_us / us, 1),
                "max_abs_err": float(np.max(np.abs(
                    np.asarray(got, np.float32) - np.asarray(want))))}),
                flush=True)


TRINITY = {"rows": 32, "kv": 8, "heads": 48, "window": 4096, "ring": 34,
           "window_layers": 4, "full_pages": 2300, "full_table": 128,
           "held": 32, "width": 3072, "touched": 12, "prompt": 12288}


def trinity_lines(label: str, module, device, peak_bytes_s: float) -> None:
    """The afmoe cell's reads through `module` and, beside the tree's, its
    experts and prefill attention alone (see the module docstring): one
    JSON line a measurement."""
    g = TRINITY
    rows, n_kv, heads, W, ring = (g["rows"], g["kv"], g["heads"],
                                  g["window"], g["ring"])
    rng = np.random.default_rng(4)
    lengths = rng.integers(1500, 13000 - BLOCK, size=rows)
    lengths[rows // 4] = 0         # a row that holds no request
    live = lengths > 0
    keys = jax.random.split(jax.random.PRNGKey(5), 6)
    draw = lambda k, shape: jax.random.normal(           # noqa: E731
        k, shape, jnp.float32).astype(jnp.bfloat16)
    q = draw(keys[0], (rows, heads, DH))
    news = [draw(k, (BLOCK, rows, n_kv, DH)) for k in keys[1:3]]
    paged = jnp.asarray(np.where(live, lengths, 0), jnp.int32)

    def line(what: str, us: float, **more) -> None:
        print(json.dumps({"device": device.device_kind, "kernel": label,
                          "what": what, "geometry": "trinity",
                          "us": round(us, 1), **more}), flush=True)

    def masked(q, keys_, values, seen):
        """Attention of every row over [rows, S] gathered tokens."""
        s = jnp.einsum("bhgd,bhds->bhgs", q.reshape(
            rows, n_kv, heads // n_kv, DH).astype(jnp.float32),
            keys_.astype(jnp.float32)) / DH ** 0.5
        p = jax.nn.softmax(jnp.where(seen[:, None, None], s, -1e30), -1)
        out = jnp.einsum("bhgs,bhds->bhgd", p, values.astype(jnp.float32))
        return jnp.where(jnp.any(seen, -1)[:, None, None, None], out,
                         0.0).reshape(rows, heads, DH)

    for what, layers, pages, width, windowed in (
            ("window_read", g["window_layers"], rows * ring + 1, ring, True),
            ("paged_read", 1, g["full_pages"], g["full_table"], False)):
        one = [draw(k, (pages, n_kv, DH, PS)) for k in keys[3:5]]
        stack = jax.jit(lambda x: jnp.tile(x[None], (layers, 1, 1, 1, 1)))
        k_pool, v_pool = stack(one[0]), stack(one[1])
        table = np.zeros((rows, width), np.int32)
        free = iter(rng.permutation(np.arange(1, pages)))
        for b in np.flatnonzero(live):
            held = min(-(-(lengths[b] + BLOCK) // PS), width)
            table[b, :held] = [next(free) for _ in range(held)]
        table = jnp.asarray(table)
        more = {"window": W, "ring": ring} if windowed else {}

        def run(q, k_pool, v_pool):
            def layer(l, carry):
                t, acc, k_tail, v_tail = carry
                out, k_tail, v_tail = module.paged_attention_in_block(
                    q, news[0][t], news[1][t], k_pool, v_pool, k_tail,
                    v_tail, table, paged, jnp.where(live, t + 1, 0),
                    layer=l, **more)
                return t, acc + out.astype(jnp.float32), k_tail, v_tail

            acc, k_tail, v_tail = jax.lax.fori_loop(
                0, BLOCK, lambda t, carry: jax.lax.fori_loop(
                    0, layers, layer, (t,) + carry)[1:],
                (jnp.zeros(q.shape, jnp.float32),
                 *module.block_tail(k_pool, rows, BLOCK)))
            return acc + k_tail[0, :, 0, 0, :1][:, :, None]

        us = (best_of_five(jax.jit(run), q, k_pool, v_pool)
              / (layers * BLOCK) * 1e6)
        # step 0 of a block against masked attention over the same pages:
        # logical page j of a row in column j % ring (the ring) or j
        got = jax.jit(lambda q, k, v: module.paged_attention_in_block(
            q, news[0][0], news[1][0], k, v,
            *module.block_tail(k, rows, BLOCK), table, paged,
            jnp.where(live, 1, 0), layer=jnp.int32(layers - 1), **more)[0])(
                q, k_pool, v_pool)
        span = ring if windowed else width
        first = (np.maximum(lengths + 1 - W, 0) // PS if windowed
                 else np.zeros(rows, np.int64))
        logical = first[:, None] + np.arange(span)[None, :]
        columns = jnp.asarray(logical % ring if windowed else logical)
        at = jnp.asarray(logical[:, :, None] * PS + np.arange(PS)).reshape(
            rows, span * PS)
        seen = at < paged[:, None]
        if windowed:
            seen = jnp.logical_and(seen, at >= (paged + 1 - W)[:, None])
        gathered = [jnp.moveaxis(pool[-1][jnp.take_along_axis(
            table, columns, 1)], 1, 3).reshape(rows, n_kv, DH, span * PS)
            for pool in (k_pool, v_pool)]
        # the step's own token, which the read puts into the tail
        gathered = [jnp.concatenate([x, new[0][:, :, :, None]], -1)
                    for x, new in zip(gathered, news)]
        want = jax.jit(masked)(q, *gathered, jnp.concatenate(
            [seen, jnp.asarray(live)[:, None]], -1))
        tokens = int(np.minimum(lengths, W).sum() if windowed
                     else lengths.sum())
        floor_us = tokens * 2 * n_kv * DH * 2 / peak_bytes_s * 1e6
        line(what, us, rows=int(live.sum()), tokens_seen=tokens,
             block=BLOCK, pages_per_fold=module.fold_of((k_pool, v_pool),
                                                        width),
             seen_tokens_share_of_peak_pct=round(100 * floor_us / us, 1),
             max_abs_err=float(np.max(np.abs(
                 np.asarray(got, np.float32) - np.asarray(want)))))
        del k_pool, v_pool, gathered
    if label != "tree":
        return

    # the tiled gated experts, a decode step of 31 rows: 12 of 32 touched
    from gofr_tpu.ops.moe_experts import decode_experts, width_tile

    D = F = g["width"]
    make = jax.jit(lambda k: (jax.random.normal(
        k, (g["held"], F, D), jnp.float32) / D ** 0.5).astype(jnp.bfloat16))
    w1, wg, w2 = (make(k) for k in jax.random.split(keys[5], 3))
    x = draw(keys[0], (rows, D))
    combine = np.zeros((rows, g["held"]), np.float32)
    combine[np.arange(rows), np.arange(rows) % g["touched"]] = 0.5
    combine = jnp.asarray(combine)
    def steps(x, w1, wg, w2):
        # STEPS calls in one program, each fed by the one before: one call
        # alone is mostly the dispatch
        def one(_, acc):
            fed = x + (acc[:, :1] * 0.0).astype(x.dtype)
            return acc + decode_experts(fed, w1, w2, combine, wg=wg)

        return jax.lax.fori_loop(0, STEPS, one,
                                 jnp.zeros((rows, D), jnp.float32))

    us = best_of_five(jax.jit(steps), x, w1, wg, w2) / STEPS * 1e6
    floor_us = g["touched"] * 3 * F * D * 2 / peak_bytes_s * 1e6
    line("moe_experts", us, rows=rows, experts_touched=g["touched"],
         tile=width_tile(F, D, 3, 2),
         touched_matrices_share_of_peak_pct=round(100 * floor_us / us, 1))
    del w1, wg, w2

    # the prefill's flash attention over one prompt of 12,288 tokens
    from gofr_tpu.models.afmoe import FLASH_BLOCKS
    from gofr_tpu.ops.flash_attention import flash_attention

    T = g["prompt"]
    qp = draw(keys[0], (1, T, heads, DH))
    kp, vp = draw(keys[1], (1, T, n_kv, DH)), draw(keys[2], (1, T, n_kv, DH))
    peak_flops = peaks.of(device.device_kind)["bf16_flops"]
    for window in (W, None):
        fn = jax.jit(lambda q, k, v: flash_attention(
            q, k, v, True, *FLASH_BLOCKS, window=window))
        us = best_of_five(fn, qp, kp, vp) * 1e6
        pairs = (T * (T + 1) // 2 if window is None
                 else W * (W + 1) // 2 + (T - W) * W)
        line("flash_prefill", us, tokens=T, window=window,
             blocks=list(FLASH_BLOCKS),
             seen_pairs_share_of_mxu_peak_pct=round(
                 100 * (4 * pairs * heads * DH / peak_flops) / (us / 1e6),
                 1))


PARTS = ("pools", "tail", "latent", "short", "trinity")   # and `rows`, asked for


def main(argv) -> None:
    device = jax.devices()[0]
    peak_bytes_s = peaks.of(device.device_kind)["hbm_bytes_per_s"]
    only = [arg[5:].split(",") for arg in argv if arg.startswith("only=")]
    parts = only[0] if only else PARTS
    groups = [int(rows) for arg in argv if arg.startswith("groups=")
              for rows in arg[7:].split(",")]
    kernels = {"tree": gofr_tpu.ops.paged_attention}
    kernels.update((label, load(label, path)) for label, path in
                   (arg.split("=", 1) for arg in argv
                    if not arg.startswith(("only=", "groups="))))
    if "pools" in parts:
        pool_lines(kernels, device, peak_bytes_s)
    for label, module in kernels.items():
        if "tail" in parts and hasattr(module, "block_tail"):
            for geometry in GEOMETRIES:
                tail_lines(label, module, geometry, device)
    for label, module in kernels.items():
        if "latent" in parts and hasattr(module, "plane_tail"):
            latent_lines(label, module, device, peak_bytes_s)
    for label, module in kernels.items():
        if "rows" in parts and hasattr(module, "plane_tail"):
            latent_lines(label, module, device, peak_bytes_s, SHORT, groups,
                         rows_only=True)
    for label, module in kernels.items():
        if "short" in parts and hasattr(module, "plane_tail"):
            latent_lines(label, module, device, peak_bytes_s, SHORT, groups)
            tail_lines(label, module, "nemotron", device, rows="short")
    for label, module in kernels.items():
        if "trinity" in parts and hasattr(module, "plane_tail"):
            trinity_lines(label, module, device, peak_bytes_s)


def pool_lines(kernels: dict, device, peak_bytes_s: float) -> None:
    """The read without a tail over internlm2's pools, bfloat16 and int8,
    under the `closed` and `chat` tables: one JSON line a module."""
    reference = gofr_tpu.ops.paged_attention.paged_attention_reference
    q = jax.random.normal(jax.random.PRNGKey(1), (B, H, DH), jnp.bfloat16)
    for dtype in (jnp.bfloat16, jnp.int8):
        k_pool, v_pool, scales = pools_of(dtype)
        for name in ("closed", "chat"):
            table, lengths, pages = table_of(name, np.random.default_rng(1))
            args = (q, k_pool, v_pool, table, lengths, *scales)
            floor_us = (pages * 2 * HKV * DH * PS * k_pool.dtype.itemsize
                        / peak_bytes_s * 1e6)
            want = np.asarray(jax.jit(lambda q, k, v, t, n, *s: reference(
                q.astype(jnp.float32), k[L - 1], v[L - 1], t, n,
                *[x[L - 1] for x in s]))(*args))
            for label, module in kernels.items():
                us = time_one(module, args)
                got = jax.jit(lambda *a: module.paged_attention(
                    *a, layer=jnp.int32(L - 1)))(*args)
                print(json.dumps({
                    "device": device.device_kind, "kernel": label,
                    "pool": str(jnp.dtype(dtype)), "table": name,
                    "live_pages": pages, "us_per_call": round(us, 1),
                    "pages_per_fold": (
                        module.fold_of((k_pool, v_pool, *scales), NP)
                        if hasattr(module, "fold_of") else 1),
                    "whole_pages_share_of_peak_pct":
                        round(100 * floor_us / us, 1),
                    "max_abs_err": float(np.max(np.abs(
                        np.asarray(got, np.float32) - want)))}), flush=True)
        del k_pool, v_pool, scales, args


if __name__ == "__main__":
    main(sys.argv[1:])
