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
  answer. `kv_planes(Hkv, dh)` is K and V of grouped-query attention (the
  llama_like and nemotron_h families); mla_moe keeps ONE plane of 1 x 576,
  a token's normed latent and its rotated shared key. What is said of
  "the pools" below is a tuple in this order.
- `kv_layers`: how many blocks keep pages: the pools' leading axis. A page
  id spans these blocks, not all blocks.
- `state_shapes(slots)`: ((shape, dtype), ...) of the arrays a sequence
  holds BESIDE its pages, fixed in size, the slot axis second
  ([layers, slots, ...]); () for a model whose only cached state is pages.
- `prefill(params, tokens [K, bucket], lengths [K], mesh)` from an empty
  state -> (last real position's logits [K, V] float32, a window
  [kv_layers, K, heads, width, bucket] a plane for the page writer, one
  [layers, K, ...] array for each of `state_shapes`: the state as of each
  row's last real token). The engine scatters pages and slot states.
- `decode(params, tokens [B], positions [B], pools, table, state, tail,
  step, mesh)` -> (logits [B, V] float32, tail, state, counters): one
  token a row, step `step` (int32) of a decode block. The pools are READ
  ONLY here, as the block found them: what the token must keep goes into
  the block's `tail`, one a plane (ops/paged_attention `plane_tail`: the
  engine makes it when the block begins and flushes it into the pages
  when the block is over), and the read attends the row's pages as of the
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

`models/llama.py` (pages only, no state, no counters, refuses nothing),
`models/nemotron_h.py` (pages for 6 blocks in 52, a recurrent state and a
convolution tail a slot, expert counters) and `models/mla_moe.py` (one
latent plane a page, expert counters) are the three families.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Tuple


@dataclasses.dataclass(frozen=True)
class Plane:
    """One plane of a page: `heads` x `width` values a token a block."""
    name: str
    heads: int
    width: int


def kv_planes(heads: int, width: int) -> Tuple[Plane, Plane]:
    """K and V of grouped-query attention."""
    return Plane("k", heads, width), Plane("v", heads, width)


@dataclasses.dataclass(frozen=True)
class PagedModel:
    family: str
    program_tag: str            # leads the step programs' names
    planes: Tuple[Plane, ...]
    kv_layers: int
    state_shapes: Callable[[int], Tuple]
    prefill: Callable
    decode: Callable
    counters: Tuple[str, ...] = ()
    refuses: Dict[str, str] = dataclasses.field(default_factory=dict)
    describe: Callable[[Dict[str, int], int], Dict[str, Any]] = (
        lambda counts, steps: {})

    @property
    def token_values(self) -> int:
        """Values a token keeps in pages, all planes and blocks."""
        return self.kv_layers * sum(p.heads * p.width for p in self.planes)

    def refuse(self, asked: Dict[str, Any]) -> None:
        """Raise for the first feature in `asked` ({feature: the value the
        caller gave}) that is switched on and that the family refuses."""
        for feature, value in asked.items():
            if value and feature in self.refuses:
                raise ValueError(
                    f"the {self.family} family refuses {feature}="
                    f"{value!r}: {self.refuses[feature]}")
