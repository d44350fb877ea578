"""The least work of A.X-K1's layers on the share this chip holds, from
shapes alone and whatever implements it. Kept with the benchmark so that no
PR that claims a gain can move it; a share computed from these can only come
out too low.

`config` is the benchmark's configuration file: the source's keys, with
`n_routed_experts` the experts HELD here and `expert_share` `[r, R]` the
share (the router scores `n_routed_experts x R` experts).
"""
from __future__ import annotations

from benchmark import flops

ITEMSIZE = 2   # the configuration states weights and the latent pool in bfloat16


def attention_parameters(c: dict) -> int:
    """q_a, q_b, kv_a, kv_b (keys' and values' halves) and o."""
    h, H = c["hidden_size"], c["num_attention_heads"]
    qk = c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
    return (h * c["q_lora_rank"] + c["q_lora_rank"] * H * qk
            + h * (c["kv_lora_rank"] + c["qk_rope_head_dim"])
            + c["kv_lora_rank"] * H * (c["qk_nope_head_dim"] + c["v_head_dim"])
            + H * c["v_head_dim"] * h)


def expert_parameters(c: dict) -> int:
    """One expert of `moe_intermediate_size`: gate, up and down."""
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def dense_ffn_parameters(c: dict) -> int:
    return 3 * c["hidden_size"] * c["intermediate_size"]


def router_parameters(c: dict) -> int:
    return c["hidden_size"] * c["n_routed_experts"] * c["expert_share"][1]


def held_pairs_per_token(c: dict) -> float:
    """Token-expert pairs a token gives the held experts when the router
    spreads evenly: `num_experts_per_tok` over the share count."""
    return c["num_experts_per_tok"] / c["expert_share"][1]


def sparse_layer_parameters_per_token(c: dict) -> float:
    """What a token is multiplied by in a sparse layer HERE: attention, the
    router, the shared experts, and its share of the routed ones."""
    return (attention_parameters(c) + router_parameters(c)
            + (c["n_shared_experts"] + held_pairs_per_token(c)) * expert_parameters(c))


def prompt_flops_per_token(c: dict) -> float:
    """The matrices a prompt token passes: the dense layers, the sparse
    layers with the routed experts at an even spread. Only a prompt's last
    token passes the head, which is left out. The attention's own products
    grow with the context, which no per-token constant holds: they are left
    out (`latent_attn_roofline` counts them for decode), so a share of the
    peak built on this reads low, by a sixth at a 4k prompt."""
    k = c["first_k_dense_replace"]
    return 2.0 * (k * (attention_parameters(c) + dense_ffn_parameters(c))
                  + (c["num_hidden_layers"] - k) * sparse_layer_parameters_per_token(c))


def answer_flops_per_token(c: dict) -> float:
    return prompt_flops_per_token(c) + 2.0 * c["vocab_size"] * c["hidden_size"]


# ------------------------------------------------------------ the kernels
def latent_row_bytes(c: dict) -> int:
    """A token's cache row in one layer: `[c_kv | k_rope]`. A layout that
    tiles better (the program pads 576 to 640) moves more and cannot raise a
    share."""
    return (c["kv_lora_rank"] + c["qk_rope_head_dim"]) * ITEMSIZE


def latent_attention_flops_per_row(c: dict) -> int:
    """One query a lane against one cached row in one layer, every head:
    the score over `rank + rope` columns and the weighted sum over `rank`."""
    return 2 * c["num_attention_heads"] * (2 * c["kv_lora_rank"] + c["qk_rope_head_dim"])


def expert_bytes(c: dict) -> int:
    return expert_parameters(c) * ITEMSIZE


def expert_flops_per_pair(c: dict) -> int:
    return 2 * expert_parameters(c)


def least_seconds(operations: float, nbytes: float, device_kind: str) -> float:
    return flops.least_seconds(operations, nbytes, device_kind)[0]
