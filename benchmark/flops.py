"""Operations and bytes that the algorithm needs, computed from shapes. Kept
with the benchmark so that no PR that claims a gain can move them.

A matrix multiplication of [m, k] by [k, n] is 2 m k n operations. Causal
attention needs half of the full score matrix. Recomputed operations (the
flash backward's second Q K^T) are not needed by the algorithm and are not
counted, so a share computed from these can only come out too low.
"""
from __future__ import annotations

import json
import os

_PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def peaks(device_kind: str) -> dict:
    """The peaks of one chip. An unknown device_kind is an error."""
    with open(_PEAKS) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device_kind {device_kind!r} in {_PEAKS}; "
                       "add the device with its source, do not default")
    return table[device_kind]


def causal_attention_train_flops(batch: int, seq: int, heads: int, head_dim: int) -> float:
    """One layer's attention, forward and backward, causal: forward Q K^T and
    P V; backward dV = P^T dO, dP = dO V^T, dQ = dS K, dK = dS^T Q. Six
    products of 2 * seq * seq * head_dim each per head, halved by the mask."""
    return 6 * 2 * batch * heads * seq * seq * head_dim / 2.0


def causal_attention_train_bytes(batch: int, seq: int, heads: int, head_dim: int,
                                 itemsize: int = 2) -> float:
    """The least HBM traffic of the same: forward reads q, k, v and writes o;
    backward reads q, k, v, o, do and writes dq, dk, dv. Twelve tensors of
    [batch, seq, heads, head_dim]; the per-row statistics are left out."""
    return 12.0 * batch * seq * heads * head_dim * itemsize


def train_flops_per_token(matmul_parameters: int, layers: int, seq: int,
                          hidden: int) -> float:
    """Forward and backward: 6 per matmul parameter, plus causal attention
    (causal_attention_train_flops over batch * seq tokens = 6 * seq * hidden
    per layer)."""
    return 6.0 * matmul_parameters + layers * 6.0 * seq * hidden


def least_seconds(flops: float, nbytes: float, device_kind: str) -> tuple:
    """The least time the chip could take and which peak bounds it."""
    p = peaks(device_kind)
    by_flops = flops / p["bf16_flops_per_s"]
    by_bytes = nbytes / p["hbm_bytes_per_s"]
    return (by_flops, "flops") if by_flops >= by_bytes else (by_bytes, "bytes")
