"""GPT-2's operations and bytes, as functions of shapes.

Each counts what the algorithm needs, whatever implements it, so that a
share of a peak cannot pass 100 %: recomputation, padding, layout copies,
masked-out work and wasted slots are all left out. ``s`` is the dict of
``weights.sizes`` (D units, F MLP width, L layers, V vocabulary, H heads).
The three whole-step counts are what ``reducers/mfu.py`` asks of every
family; a kernel's cost function (named by ``"cost"`` in
``layer_metrics/<metric>.json``) takes the traced window's facts and
returns that window's ``(flops, bytes)``.
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


def _flash_fwd_one(s, batch, seq, itemsize=2):
    """One causal flash-forward call over (batch, H, seq, D/H): the causal
    half of 4 B H S^2 (D/H) FLOPs; q, k, v read and o written once."""
    flops = 2 * batch * seq * seq * s["D"]
    nbytes = 4 * batch * seq * s["D"] * itemsize
    return flops, nbytes


def flash_fwd_call(f):
    """The flash-forward kernel's work in a traced training window ``f``:
    one call a layer and step."""
    s = f["sizes"]
    flops, nbytes = _flash_fwd_one(s, f["batch"], f["sequence"])
    calls = s["L"] * f["steps"]
    return flops * calls, nbytes * calls
