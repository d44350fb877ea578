"""The least work of Command A+'s layers on the share this chip holds, from
shapes alone and whatever implements it. Kept with the benchmark so that no
PR that claims a gain can move it; a share computed from these can only come
out too low.

`config` is the benchmark's configuration file: the source's keys, with
`num_experts` the experts HELD here and `expert_share` `[r, R]` the share
(the router scores `num_experts x R` experts).
"""
from __future__ import annotations

from benchmark import flops

ITEMSIZE = 2   # the configuration states weights and both page pools in bfloat16
WINDOW = "sliding_attention"


def attention_parameters(c: dict) -> int:
    """q and o over the query heads, k and v over the K/V heads."""
    d = c["head_dim"]
    return 2 * c["hidden_size"] * d * (c["num_attention_heads"] + c["num_key_value_heads"])


def expert_parameters(c: dict) -> int:
    """One expert of `intermediate_size`, routed or shared: gate, up, down."""
    return 3 * c["hidden_size"] * c["intermediate_size"]


def router_parameters(c: dict) -> int:
    return c["hidden_size"] * c["num_experts"] * c["expert_share"][1]


def held_pairs_per_token(c: dict) -> float:
    """Token-expert pairs a token gives the held experts when the router
    spreads evenly: `num_experts_per_tok` over the share count."""
    return c["num_experts_per_tok"] / c["expert_share"][1]


def layer_parameters_per_token(c: dict) -> float:
    """What a token is multiplied by in a layer HERE: attention, the router,
    the shared experts, and its share of the routed ones."""
    return (attention_parameters(c) + router_parameters(c)
            + (c["num_shared_experts"] + held_pairs_per_token(c)) * expert_parameters(c))


def prompt_flops_per_token(c: dict) -> float:
    """The matrices a prompt token passes, the routed experts at an even
    spread. Only a prompt's last token passes the head, which is left out.
    The attention's own products grow with the context, which no per-token
    constant holds: they are left out (`win_attn_roofline` counts decode's),
    so a share of the peak built on this reads low: at a 4k context by a
    quarter (128 heads x 128 x 4 operations a key a layer, 1.1 GFLOP a token
    against 3.2), in a global layer at 32k by far more."""
    return 2.0 * c["num_hidden_layers"] * layer_parameters_per_token(c)


def answer_flops_per_token(c: dict) -> float:
    return prompt_flops_per_token(c) + 2.0 * c["vocab_size"] * c["hidden_size"]


# ------------------------------------------------------------ the kernels
def layer_counts(c: dict) -> tuple:
    """(window layers, global layers)."""
    n = sum(1 for kind in c["layer_types"] if kind == WINDOW)
    return n, len(c["layer_types"]) - n


def kv_row_bytes(c: dict) -> int:
    """A token's K row and V row in one layer."""
    return 2 * c["num_key_value_heads"] * c["head_dim"] * ITEMSIZE


def attention_flops_per_row(c: dict) -> int:
    """One query a lane against one cached row in one layer, every query
    head: the score and the weighted sum over `head_dim`."""
    return 4 * c["num_attention_heads"] * c["head_dim"]


def expert_bytes(c: dict) -> int:
    return expert_parameters(c) * ITEMSIZE


def expert_flops_per_pair(c: dict) -> int:
    return 2 * expert_parameters(c)


def decode_attention_rows(pages_live: int, window_pages_live: int, lanes: int,
                          page: int, window_layers: int, full_layers: int) -> int:
    """The fewest cached rows a decode step's attention must read over all
    layers, from what its span says: a global layer every row up to each
    lane's position (its last page counted as one row), a window layer the
    rows the window covers (a lane's first and last window page counted as
    one page together: at a depth past the window they hold `sliding_window`
    rows with the pages between, before it a page less one row)."""
    return (full_layers * ((pages_live - lanes) * page + lanes)
            + window_layers * (window_pages_live - lanes) * page)


def least_seconds(operations: float, nbytes: float, device_kind: str) -> float:
    return flops.least_seconds(operations, nbytes, device_kind)[0]
