"""Operations and bytes, as functions of shapes.

Each counts what the algorithm needs, whatever implements it, so that a
share of a peak cannot pass 100 %: recomputation, padding, layout copies,
masked-out work and wasted slots are all left out. ``s`` is the dict of
``weights.sizes`` (D units, F MLP width, L layers, V vocabulary, H heads).
"""
from __future__ import annotations


def block_matmul_params(s):
    """Parameters of the L blocks' eight dense products (q, k, v, out:
    4 D^2; the MLP: 2 D F). Embeddings, biases and LayerNorms are left
    out: they multiply nothing."""
    return s["L"] * (4 * s["D"] ** 2 + 2 * s["D"] * s["F"])


def token_forward_flops(s, keys, with_head):
    """One token through the model with ``keys`` positions to attend to
    (its own included): 2 FLOPs per matmul parameter, 4 D per key and
    layer for QK^T and PV, and 2 V D for a row of logits where one is
    needed (``with_head``). Softmax, LayerNorm and GELU are left out."""
    f = 2 * block_matmul_params(s) + 4 * s["L"] * s["D"] * keys
    return f + (2 * s["V"] * s["D"] if with_head else 0)


def prompt_forward_flops(s, n):
    """A prompt of ``n`` tokens: each token attends causally to itself and
    what precedes it; one row of logits (the last) is needed."""
    attn = 4 * s["L"] * s["D"] * n * (n + 1) // 2
    return 2 * block_matmul_params(s) * n + attn + 2 * s["V"] * s["D"]


def train_step_flops(s, batch, seq):
    """Forward and backward of ``batch`` sequences of ``seq`` tokens:
    6 FLOPs per matmul parameter and token, the LM head among them; causal
    attention 2 S^2 D forward per layer and sequence (half of the full
    4 S^2 D) and twice that backward. Recomputation is not counted."""
    dense = 6 * (block_matmul_params(s) + s["V"] * s["D"]) * batch * seq
    attn = 3 * 2 * seq * seq * s["D"] * s["L"] * batch
    return dense + attn


def flash_fwd_call(s, batch, seq, itemsize=2):
    """One causal flash-forward call over (batch, H, seq, D/H): the causal
    half of 4 B H S^2 (D/H) FLOPs; q, k, v read and o written once."""
    flops = 2 * batch * seq * seq * s["D"]
    nbytes = 4 * batch * seq * s["D"] * itemsize
    return flops, nbytes


def paged_decode_tokens(s, contexts, itemsize=2):
    """The paged decode kernel's work for decoded tokens that attended to
    ``contexts`` positions each, over all L layers: K and V of those
    positions read once (2 ctx D), q read and out written (2 D); 4 D
    FLOPs per key. Pages a slot holds but the token does not attend to,
    and slots that decode nothing, are left out."""
    total = sum(contexts)
    n = len(contexts)
    flops = s["L"] * 4 * s["D"] * total
    nbytes = s["L"] * itemsize * (2 * s["D"] * total + 2 * s["D"] * n)
    return flops, nbytes


def least_seconds(flops, nbytes, peaks):
    """The least time the chip could take, and which peak bounds it."""
    t_f = flops / peaks["bf16_flops_per_s"]
    t_b = nbytes / peaks["hbm_bytes_per_s"]
    return (t_f, "compute") if t_f >= t_b else (t_b, "hbm")
