"""The ``nemotronh`` family's operations and bytes, as functions of
shapes.

Each counts what the algorithm needs, whatever implements it, so that a
share of a peak cannot pass 100 %: two FLOPs a parameter a token
multiplies, a routed layer counting its router, the shared expert and the
picks that fall on the held experts (``num_experts_per_tok`` x held /
all: 3 of 6 on a chip that holds 64 of 128), the head on one row a
prompt; attention ``4 Hq dh`` a key and layer, causal halves for a
prompt; the Mamba-2 recurrence ``4 H P N`` a position and layer (the
state's update and its read-out) and the convolution's taps. Left out:
norms, softplus, ``exp``, softmax, sigmoid, top-k, the gate, padding,
masked-out work, gathers, empty slots. ``s`` is the dict of
``weights.sizes``.
"""
from __future__ import annotations

from chipbench.families.nemotronh import weights as W


def _mixer_params(s, kind):
    """Parameters a row multiplies in one mixer of ``kind``."""
    d = s["D"]
    if kind == W.MAMBA:
        di = s["H"] * s["P"]
        return d * (2 * di + 2 * s["G"] * s["N"] + s["H"]) + di * d
    if kind == W.EXPERTS:
        picks = s["top_k"] * s["E_held"] / s["E_all"]
        return d * s["E_all"] + 2 * d * (s["F"] * picks + s["Fs"])
    return d * (s["Hq"] + 2 * s["Hkv"]) * s["dh"] + s["Hq"] * s["dh"] * d


def _scan_flops(s):
    """One position through one Mamba-2 layer's recurrence and
    convolution."""
    cd = s["H"] * s["P"] + 2 * s["G"] * s["N"]
    return 4 * s["H"] * s["P"] * s["N"] + 2 * s["K"] * cd


def _row_flops(s):
    f = sum(2 * _mixer_params(s, kind) for kind in s["pattern"])
    return f + s["pattern"].count(W.MAMBA) * _scan_flops(s)


def _per_key(s):
    return s["pattern"].count(W.ATTENTION) * 4 * s["Hq"] * s["dh"]


def token_forward_flops(s, keys, with_head):
    """One decoded token with ``keys`` positions in context (its own
    included)."""
    f = _row_flops(s) + _per_key(s) * keys
    return f + (2 * s["V"] * s["D"] if with_head else 0)


def prompt_forward_flops(s, n):
    """A prompt of ``n`` tokens, each attending causally; one row of
    logits."""
    return (_row_flops(s) * n + _per_key(s) * n * (n + 1) // 2
            + 2 * s["V"] * s["D"])


def train_step_flops(s, batch, seq):
    raise SystemExit("chipbench: the nemotronh family is served, not "
                     "trained")


def ssd_chunk_scan_call(f):
    """The ``ssd_chunk_scan`` kernel's work in a traced serving window
    ``f``: the useful positions of the prompts whose first token fell in
    it, ``sum(prompts_done)`` a Mamba-2 layer, each ``4 H P N`` FLOPs;
    bytes, float32: ``x`` and ``y`` (H P each) and ``dt`` (H) a position,
    ``B`` and ``C`` (G N each), and a layer's states (H P N) taken up and
    handed on once a prompt. A floor on what a padded chunk hands the
    kernel (its padded positions are not counted), and a prompt whose
    earlier chunks ran before the window counts them too."""
    s = f["sizes"]
    layers = s["pattern"].count(W.MAMBA)
    tokens = sum(f["prompts_done"]) * layers
    prompts = len(f["prompts_done"]) * layers
    hpn = s["H"] * s["P"] * s["N"]
    flops = tokens * 4 * hpn
    nbytes = 4 * (tokens * (2 * s["H"] * s["P"] + s["H"]
                            + 2 * s["G"] * s["N"]) + prompts * 2 * hpn)
    return flops, nbytes
