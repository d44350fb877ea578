"""GPT-2 as published (Radford et al. 2019; the Hugging Face `gpt2`
modeling code), forward pass and next-token loss, in plain float32
jax.numpy: no kernel, no cache, no batching tricks, matmul precision
"highest" (on a TPU a float32 product otherwise runs in bfloat16).

It shares nothing with paddle_tpu but the weights, which `weights_of` copies
out of the program's model so that both sides compute on the same numbers.
Departure from the source: none in the mathematics. The vocabulary may be
padded (the configuration says so); pad rows take part in the softmax as they
do in the program.
"""
from __future__ import annotations

import math


def weights_of(model) -> dict:
    """A float32 copy of a paddle_tpu GPTForCausalLM's weights as a plain
    pytree. Linear weights are [in, out], as the program stores them."""
    import jax.numpy as jnp

    def f32(p):
        return jnp.asarray(p._value, jnp.float32)

    blocks = []
    for blk in model.gpt.h:
        blocks.append({
            "ln1_w": f32(blk.ln_1.weight), "ln1_b": f32(blk.ln_1.bias),
            "qkv_w": f32(blk.attn.qkv_proj.weight), "qkv_b": f32(blk.attn.qkv_proj.bias),
            "out_w": f32(blk.attn.out_proj.weight), "out_b": f32(blk.attn.out_proj.bias),
            "ln2_w": f32(blk.ln_2.weight), "ln2_b": f32(blk.ln_2.bias),
            "fc1_w": f32(blk.mlp.fc1.weight), "fc1_b": f32(blk.mlp.fc1.bias),
            "fc2_w": f32(blk.mlp.fc2.weight), "fc2_b": f32(blk.mlp.fc2.bias),
        })
    return {"wte": f32(model.gpt.embeddings.word_embeddings.weight),
            "wpe": f32(model.gpt.embeddings.position_embeddings.weight),
            "lnf_w": f32(model.gpt.ln_f.weight), "lnf_b": f32(model.gpt.ln_f.bias),
            "blocks": blocks}


def _layer_norm(x, w, b, eps):
    import jax.numpy as jnp

    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * w + b


def _gelu_new(x):
    import jax.numpy as jnp

    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _block(x, blk, causal, heads: int, eps: float):
    import jax
    import jax.numpy as jnp

    b, s, width = x.shape
    d = width // heads
    h = _layer_norm(x, blk["ln1_w"], blk["ln1_b"], eps)
    # the program's fused projection is laid out [heads, (q, k, v), d]
    qkv = (h @ blk["qkv_w"] + blk["qkv_b"]).reshape(b, s, heads, 3, d)
    q, k, v = qkv[..., 0, :], qkv[..., 1, :], qkv[..., 2, :]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(d)
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    att = jnp.einsum("bhqk,bkhd->bqhd", probs, v).reshape(b, s, width)
    x = x + att @ blk["out_w"] + blk["out_b"]
    h = _layer_norm(x, blk["ln2_w"], blk["ln2_b"], eps)
    return x + _gelu_new(h @ blk["fc1_w"] + blk["fc1_b"]) @ blk["fc2_w"] + blk["fc2_b"]


def hidden_states(weights: dict, ids, heads: int, eps: float):
    """[batch, seq] token ids -> [batch, seq, hidden] after the final norm."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        s = ids.shape[1]
        x = weights["wte"][ids] + weights["wpe"][:s][None]
        causal = jnp.tril(jnp.ones((s, s), bool))
        # each block is recomputed in the backward pass, so that block_grads
        # at a training batch holds one block's float32 intermediates at a time
        block = jax.checkpoint(_block, static_argnums=(3, 4))
        for blk in weights["blocks"]:
            x = block(x, blk, causal, heads, eps)
        return _layer_norm(x, weights["lnf_w"], weights["lnf_b"], eps)


def logits_at(weights: dict, hidden):
    """Tied output head: [..., hidden] -> [..., vocab]."""
    import jax

    with jax.default_matmul_precision("highest"):
        return hidden @ weights["wte"].T


def loss(weights: dict, ids, heads: int, eps: float):
    """Mean next-token cross entropy over the batch's seq - 1 predictions."""
    import jax
    import jax.numpy as jnp

    logits = logits_at(weights, hidden_states(weights, ids, heads, eps))[:, :-1]
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, ids[:, 1:, None], axis=-1)[..., 0]
    return -picked.mean()


def block_grads(weights: dict, ids, heads: int, eps: float, names=("qkv_w", "out_w"),
                blocks=None):
    """The loss, and its gradient with respect to the named matrices of the
    blocks with the given indices (all, if None): {index: {name: array shaped
    as the weight}}."""
    import jax

    chosen = range(len(weights["blocks"])) if blocks is None else blocks

    def of(picked):
        merged = [dict(blk, **picked.get(i, {})) for i, blk in enumerate(weights["blocks"])]
        return loss(dict(weights, blocks=merged), ids, heads, eps)

    picked = {i: {n: weights["blocks"][i][n] for n in names} for i in chosen}
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(of)(picked)


def relative_error(got, want) -> float:
    """|got - want| / |want| in the Euclidean norm, in float64 on the host."""
    import numpy as np

    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))
