"""The least work of Brumby's layers, from shapes alone and whatever
implements it. Kept with the benchmark so that no PR that claims a gain can
move it; a share computed from these can only come out too low.

The state of one key/value head is the symmetric second power of a
`head_dim` vector against a value and a one: `sym(head_dim) x (head_dim + 1)`
float32 numbers, `sym(128) = 128 x 129 / 2 = 8256`. A layout that tiles
better (the program keeps 8704 x 136) moves more bytes than this and cannot
raise a share.
"""
from __future__ import annotations

from benchmark import flops

STATE_ITEMSIZE = 4   # the configuration states the state in float32


def sym(head_dim: int) -> int:
    return head_dim * (head_dim + 1) // 2


def state_bytes(config: dict) -> int:
    """One lane's state in one layer: every key/value head's S and z."""
    d = config["head_dim"]
    return config["num_key_value_heads"] * sym(d) * (d + 1) * STATE_ITEMSIZE


def decode_state_bytes(config: dict, live_lanes: int) -> int:
    """One decode step: every live lane's state in every layer read once
    and written once."""
    return 2 * live_lanes * config["num_hidden_layers"] * state_bytes(config)


def retention_flops_per_token(config: dict) -> int:
    """The recurrence's count for one token, all layers: each key/value head
    adds phi(k) (x) [v, 1] to its state and each query head reads it, 2 x
    sym(d) x (d + 1) operations apiece. Recomputation inside a chunk (the
    masked quadratic part) is not counted."""
    d = config["head_dim"]
    heads = config["num_attention_heads"] + config["num_key_value_heads"]
    return config["num_hidden_layers"] * 2 * sym(d) * (d + 1) * heads


def layer_matmul_parameters(config: dict) -> int:
    """What a token is multiplied by in one layer: q, k, v, o, the gate's
    projection, gate, up and down."""
    h, d, f = config["hidden_size"], config["head_dim"], config["intermediate_size"]
    hq, hkv = config["num_attention_heads"], config["num_key_value_heads"]
    return 2 * h * hq * d + 2 * h * hkv * d + h * hkv + 3 * h * f


def head_parameters(config: dict) -> int:
    """The untied output head (the embedding is a look-up)."""
    return config["vocab_size"] * config["hidden_size"]


def prompt_flops_per_token(config: dict) -> int:
    """A prompt token passes the layers; only a prompt's last token passes
    the head, which is left out."""
    return (2 * config["num_hidden_layers"] * layer_matmul_parameters(config)
            + retention_flops_per_token(config))


def answer_flops_per_token(config: dict) -> int:
    return prompt_flops_per_token(config) + 2 * head_parameters(config)


def bytes_seconds(nbytes: float, device_kind: str) -> float:
    return nbytes / flops.peaks(device_kind)["hbm_bytes_per_s"]


def flops_seconds(operations: float, device_kind: str) -> float:
    return operations / flops.peaks(device_kind)["bf16_flops_per_s"]
