"""The model protocol of the paged engine: what `tpu/paging.py` asks of a
model family, only as wide as the paged floating-point path needs.

A config object answers `paged_model()` with a `PagedModel`. The engine
owns every device array (the page pools, the per-slot state, the loop's
token / position / temperature vectors), donates them to the step programs
and takes them back; the model says what state there is and computes on
it:

- `planes`: what a page HOLDS, a `Plane(name, heads, width)` each: the
  engine keeps one pool a plane, [kv_layers, pages, heads, width,
  page_size], and allocates, writes, flushes, plans and reports by this
  answer. `kv_planes(Hkv, dh)` is K and V of grouped-query attention; a
  family may keep other planes (one narrow latent plane, say), and a plane
  may have a STRIDE: a column every `stride` tokens instead of a value a
  token ([kv_layers, pages, heads, page_size / stride, width]: the
  compressed keys of models/sparse_linear.py), written by the prefill's
  window and flushed from a decode block's tail like the others, a column
  when the tokens it speaks of are all there. What is said of "the pools"
  below is a tuple in this order.
- `groups`: the blocks that keep pages, in PAGE GROUPS, a `PageGroup(name,
  layers, window)` each: blocks that share a table, an allocator and a
  reservation. A page id spans its group's blocks. `window` None, a
  sequence keeps every token (`pages_for(prompt + max_new)` pages); a
  window of W tokens, a block attends the last W only and a sequence keeps
  a RING of `ring(page_size)` pages, the token at position p in ring column
  `(p // page_size) % ring` (tpu/paging.py). Every group holds the
  family's `planes`; the engine keeps one pool a plane a group,
  [group layers, pages, heads, width, page_size], group by group.
  `one_group(n)` is what a family whose blocks all keep every token
  answers; one with window blocks answers a group each (`full`,
  `window`). `kv_layers` (the blocks that keep pages, all groups) and
  `token_values` follow from the groups.
- `state_shapes(slots)`: ((shape, dtype), ...) of the arrays a sequence
  holds BESIDE its pages, fixed in size, the slot axis second
  ([layers, slots, ...]); () for a model whose only cached state is pages.
- `prefill(params, tokens [K, bucket], lengths [K], mesh)` from an empty
  state -> (last real position's logits [K, V] float32, a window
  [group layers, K, heads, width, bucket] a plane a group (group-major,
  as the pools lie; [group layers, K, heads, bucket / stride, width] for
  a plane with a stride) for the page writer, one [layers, K, ...] array for
  each of `state_shapes`: the state as of each row's last real token).
  The engine scatters pages (a window group's last ring of the prompt
  only) and slot states.
- `decode(params, tokens [B], positions [B], pools, table, state, tail,
  step, mesh)` -> (logits [B, V] float32, tail, state, counters): one
  token a row, step `step` (int32) of a decode block. A family of one
  group takes its pools, its table and its tail as they are; a family of
  several takes `pools` and `tail` group-major and `table` a tuple, one
  a group. The pools are READ
  ONLY here, as the block found them: what the token must keep goes into
  the block's `tail`, one a plane (ops/paged_attention `plane_tail`: the
  engine makes it when the block begins and flushes it into the pages
  when the block is over; a plane with a stride has [layers, B, heads,
  ceil(block / stride), width], the columns the block's steps complete in
  order), and the read attends the row's pages as of the
  block's start plus the tail's first step + 1 tokens
  (`paged_attention_in_block` and `ops/mla_read` do both). State is
  updated in place. `counters` is
  an int32 vector named by `counters` (None when the family counts
  nothing); the engine sums it over a block's steps and carries it to the
  host on the block's own token copy.
- `refuses`: {engine feature: reason} the family cannot serve yet; the
  engine refuses each BY NAME at construction (docs/model-families.md).
  A config MAY carry `kv_dtype` (a lower-precision page pool); the engine
  takes a config without it as pages in the model's dtype.
- `describe(counts, steps)`: what `/debug/engine` shows of the family:
  static facts and what it makes of its counters' sums ({name: sum} over
  `steps` decode steps).

Who implements it: `models/families.py` lists the family modules;
docs/model-families.md says what each keeps in pages, beside them, and
counts.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple


@dataclasses.dataclass(frozen=True)
class Plane:
    """One plane of a page: `heads` x `width` values a token a block, or,
    with a `stride`, a COLUMN of `heads` x `width` values for every
    `stride` tokens: column j says something of the tokens [stride j,
    stride j + span) and exists once the sequence holds them all."""
    name: str
    heads: int
    width: int
    stride: int = 1
    span: int = 1

    def columns(self, tokens):
        """Columns that exist in a sequence of `tokens` tokens (an int or
        an array of them): every token's for a plane without a stride."""
        if self.stride == 1:
            return tokens
        short = tokens - self.span + self.stride
        return short * (short > 0) // self.stride

    def describe(self) -> dict:
        """What `/debug/engine` shows of the plane: its stride and span
        only where it has one."""
        said = dataclasses.asdict(self)
        return said if self.stride > 1 else {
            k: said[k] for k in ("name", "heads", "width")}

    def pool_shape(self, layers: int, pages: int, page_size: int) -> tuple:
        """The pool the engine keeps for this plane in a group of `layers`
        blocks: token-minor for a plane of a value a token ([.., heads,
        width, page_size]: ops/paged_attention.py), width-minor for one
        with a stride ([.., heads, page_size / stride, width]: a column is
        read and written whole)."""
        if self.stride == 1:
            return (layers, pages, self.heads, self.width, page_size)
        return (layers, pages, self.heads, page_size // self.stride,
                self.width)


def kv_planes(heads: int, width: int) -> Tuple[Plane, Plane]:
    """K and V of grouped-query attention."""
    return Plane("k", heads, width), Plane("v", heads, width)


def ring_pages(window: int, page_size: int) -> int:
    """Pages a sequence holds at most in a group whose blocks attend the
    last `window` tokens: the window, the page it starts inside, and the
    page a decode block (at most a page of steps) may cross into."""
    return -(-window // page_size) + 2


@dataclasses.dataclass(frozen=True)
class PageGroup:
    """Blocks that share a table, an allocator and a reservation.
    `window` None: a sequence keeps every token; W: its last W."""
    name: str
    layers: int
    window: Optional[int] = None

    def ring(self, page_size: int) -> Optional[int]:
        """`ring_pages` of the group's window; None for a group without
        one."""
        if self.window is None:
            return None
        return ring_pages(self.window, page_size)


def one_group(layers: int) -> Tuple[PageGroup, ...]:
    """The groups of a family whose blocks all keep every token."""
    return (PageGroup("pages", layers),)


@dataclasses.dataclass(frozen=True)
class PagedModel:
    family: str
    program_tag: str            # leads the step programs' names
    planes: Tuple[Plane, ...]
    groups: Tuple[PageGroup, ...]
    state_shapes: Callable[[int], Tuple]
    prefill: Callable
    decode: Callable
    counters: Tuple[str, ...] = ()
    refuses: Dict[str, str] = dataclasses.field(default_factory=dict)
    describe: Callable[[Dict[str, int], int], Dict[str, Any]] = (
        lambda counts, steps: {})

    @property
    def kv_layers(self) -> int:
        """Blocks that keep pages, all groups."""
        return sum(group.layers for group in self.groups)

    @property
    def plane_values(self) -> int:
        """Values a token keeps in ONE block's pages, all planes (a plane
        with a stride its share of a column)."""
        return sum(p.heads * p.width // p.stride for p in self.planes)

    @property
    def token_values(self) -> int:
        """Values a token keeps in pages, all planes and blocks, while
        every group still holds it."""
        return self.kv_layers * self.plane_values

    def sequence_values(self, tokens: int, page_size: int = 128) -> int:
        """Values a sequence of `tokens` keeps in pages: every token in a
        group without a window, at most the ring in a window group."""
        held = sum(group.layers * (
            tokens if group.window is None
            else min(tokens, group.ring(page_size) * page_size))
            for group in self.groups)
        return held * self.plane_values

    def refuse(self, asked: Dict[str, Any]) -> None:
        """Raise for the first feature in `asked` ({feature: the value the
        caller gave}) that is switched on and that the family refuses."""
        for feature, value in asked.items():
            if value and feature in self.refuses:
                raise ValueError(
                    f"the {self.family} family refuses {feature}="
                    f"{value!r}: {self.refuses[feature]}")
