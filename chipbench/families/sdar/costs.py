"""The ``sdar`` family's operations, as functions of shapes.

Each counts what the algorithm needs, whatever implements it, so that a
share of a peak cannot pass 100 %: two FLOPs a parameter a row multiplies
(attention's four projections, the router, ``K`` experts of three
matrices); attention ``4 H dh`` a key a row and layer (scores and the
weighted values). Generation by diffusion over blocks of ``B`` runs a
token's row ``steps`` times while its block is denoised and once more when
the block is committed, and the head on the ``steps`` denoised rows; a
prompt's rows once, under the block-causal mask (a row sees its own block
and every block before it), with no head. Left out: norms, rotary,
softmax, top-k, the confidence and the ranking, padding, masked-out rows,
empty slots, and a request's last block, which is never committed.
``s`` is the dict of ``weights.sizes``.
"""
from __future__ import annotations


def _layer_params(s):
    """Parameters a row multiplies in one layer."""
    d, dh = s["D"], s["dh"]
    n = d * dh * (2 * s["H"] + 2 * s["Hkv"])       # q, k, v, o
    return n + d * s["E"] + 3 * d * s["FE"] * s["K"]


def _attn_per_key(s):
    return s["L"] * 4 * s["H"] * s["dh"]


def token_forward_flops(s, keys, with_head):
    """One generated token with ``keys`` positions in context: ``steps +
    1`` row forwards over them (its block's steps and its commit), and
    the head on its ``steps`` denoised rows."""
    rows = s["steps"] + 1
    f = rows * (2 * s["L"] * _layer_params(s) + _attn_per_key(s) * keys)
    return f + (s["steps"] * 2 * s["V"] * s["D"] if with_head else 0)


def prompt_forward_flops(s, n):
    """The prompt's ``n`` tokens of whole blocks, each row attending its
    own block and those before it; no head (its first token comes from
    its first block)."""
    b = s["B"]
    n -= n % b
    keys = sum(min(n, (i // b + 1) * b) for i in range(n))
    return 2 * s["L"] * _layer_params(s) * n + _attn_per_key(s) * keys


def train_step_flops(s, batch, seq):
    raise SystemExit("chipbench: the sdar family is served, not trained")


def paged_rows_call(facts):
    """The held-pages reader's work in the traced window, a floor: each
    block is read ``steps + 1`` times (its steps and its commit), each
    read of all its keys (``2 Hkv dh`` bytes a key and layer, K and V)
    and ``4 H dh`` FLOPs a key and query row, ``B`` rows, a layer. Counted
    from the generated tokens' contexts (``decode_contexts``), ``(steps +
    1) / B`` reads a token; taken off, each at the window's LONGEST
    context (no read it stands for reads more keys): the reads that may
    have run outside the window (a slot's first block's steps, ``steps``
    reads a slot, and its last commit, one a slot) and the commits of
    requests' last blocks, which never run (one a request that ended in
    the window: at most one a request begun in it and one a slot)."""
    s = facts["sizes"]
    ctx = facts["decode_contexts"]
    if not ctx:
        return 0.0, 0.0
    b, reads_per_block = s["B"], s["steps"] + 1
    keys = sum(ctx) * reads_per_block / b
    drop = (reads_per_block + 1) * facts["max_slots"] \
        + len(facts["prompts_done"])
    keys = max(0.0, keys - drop * max(ctx))
    flops = s["L"] * 4 * s["H"] * s["dh"] * b * keys
    nbytes = s["L"] * 2 * s["Hkv"] * s["dh"] * 2 * keys
    return flops, nbytes
