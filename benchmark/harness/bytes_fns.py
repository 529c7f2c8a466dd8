"""The least bytes the algorithm needs: what the roofline shares are
measured against. From the lengths each dispatch really had, never from
padded pages or means.

Decode read of the paged cache, one step over the live rows: every live
token's K and V once, each row's q in and its output out. Decode write: the
new token's K and V values only. The page read-modify-write that the
program's write kernel does today is the implementation's cost, not the
algorithm's need, so that share reads very low until the write is fused
into the read, and a change that stops re-reading the page cannot push it
over 100 %.
"""


def paged_read_bytes(live_tokens: int, rows: int, n_layers: int,
                     n_kv_heads: int, n_heads: int, head_dim: int,
                     kv_itemsize: int = 2, act_itemsize: int = 2) -> int:
    """One decode step, all layers: live_tokens = the sum over the live rows
    of their context lengths at that step."""
    kv = 2 * live_tokens * n_kv_heads * head_dim * kv_itemsize
    q_and_out = 2 * rows * n_heads * head_dim * act_itemsize
    return n_layers * (kv + q_and_out)


def paged_write_bytes(rows: int, n_layers: int, n_kv_heads: int,
                      head_dim: int, kv_itemsize: int = 2) -> int:
    """One decode step, all layers: each live row's new K and V."""
    return n_layers * 2 * rows * n_kv_heads * head_dim * kv_itemsize


def weight_bytes(dims: dict, itemsize: int = 2) -> int:
    """The matrices a decode step has to read once: the layers and the
    head (the embedding is a gather of `rows` rows, left out)."""
    per_layer = (dims["D"] * dims["H"] * dims["dh"] * 2
                 + dims["D"] * dims["Hkv"] * dims["dh"] * 2
                 + 3 * dims["D"] * dims["F"])
    return itemsize * (dims["L"] * per_layer + dims["D"] * dims["V"])
