"""The plain reference: GPT-2 in ``jax.numpy`` float32, every product at
``Precision.HIGHEST``, no kernel, no cache, no batching tricks.

It follows the published GPT-2 description (pre-norm blocks, learned
positions, causal softmax attention scaled by 1/sqrt(head), LM head over
the final LayerNorm) with one departure that the configuration files list
under ``assumed``: the MLP's GELU is the exact (erf) form, which is what
``activation_function`` says in the as-run configuration.

It imports nothing of the program. ``lowp`` computes the same thing with
every dense product's operands, and K and V, rounded to the nearest
precision below the bf16 the configurations state: ``"int8"`` (symmetric,
one scale per row) or ``"fp8"`` (e4m3's four significant bits, the exponent
left free as a per-tensor scale would leave it). That is the *control* of
"how correct is decided", never used by a benchmark run.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.families.gpt2 import weights as W

HI = jax.lax.Precision.HIGHEST


def _fq(x, lowp):
    """Round ``x`` to ``lowp`` along its last axis, with a straight-through
    gradient so that the control can be trained."""
    if lowp == "int8":
        scale = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 127.0
        scale = jnp.where(scale > 0, scale, 1.0)
        q = jnp.clip(jnp.round(x / scale), -127, 127) * scale
    elif lowp == "fp8":
        m, e = jnp.frexp(x)                   # m in [0.5, 1): 4 bits kept
        q = jnp.ldexp(jnp.round(m * 16.0) / 16.0, e)
    else:
        raise ValueError(f"no control precision {lowp!r}")
    return x + jax.lax.stop_gradient(q - x)


def _ln(x, g, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * g + b


def _dense(x, w, b, lowp):
    """``x @ w.T + b`` with ``w`` stored (out, in)."""
    if lowp:
        x, w = _fq(x, lowp), _fq(w, lowp)
    return jnp.einsum("...i,oi->...o", x, w, precision=HI) + b


def _block(x, lw, n_head, eps, lowp):
    b, t, d = x.shape
    h = _ln(x, lw["ln1_g"], lw["ln1_b"], eps)

    def heads(name):
        y = _dense(h, lw[name + "_w"], lw[name + "_b"], lowp)
        return y.reshape(b, t, n_head, d // n_head).transpose(0, 2, 1, 3)

    q, k, v = heads("q"), heads("k"), heads("v")
    if lowp:
        k, v = _fq(k, lowp), _fq(v, lowp)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k, precision=HI)
    s = s / jnp.sqrt(jnp.float32(d // n_head))
    causal = jnp.tril(jnp.ones((t, t), bool))
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    a = jnp.einsum("bhqk,bhkd->bhqd", p, v, precision=HI)
    a = a.transpose(0, 2, 1, 3).reshape(b, t, d)
    x = x + _dense(a, lw["o_w"], lw["o_b"], lowp)
    h = _ln(x, lw["ln2_g"], lw["ln2_b"], eps)
    h = jax.nn.gelu(_dense(h, lw["f1_w"], lw["f1_b"], lowp),
                    approximate=False)
    return x + _dense(h, lw["f2_w"], lw["f2_b"], lowp)


def hidden(w, tokens, n_head, eps, lowp=None):
    """Final-LayerNorm hidden states (B, T, D) of ``tokens`` (B, T)."""
    t = tokens.shape[1]
    x = w["wte"][tokens] + w["wpe"][:t]
    layers = {n: w[n] for n in W.LAYER_NAMES}
    block = jax.checkpoint(
        lambda x, lw: _block(x, lw, n_head, eps, lowp))
    x, _ = jax.lax.scan(lambda x, lw: (block(x, lw), None), x, layers)
    return _ln(x, w["lnf_g"], w["lnf_b"], eps)


def _head(w, h, lowp):
    return _dense(h, w["head"], 0.0, lowp)


# ---------------------------------------------------------------------------
# serving: the gap of each served token under the reference's best logit
# ---------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("n_head", "eps", "n_max",
                                             "control"))
def _served_gaps(w, tokens, start, served, n_valid, *, n_head, eps, n_max,
                 control=None):
    """One request: ``tokens`` (1, T) is the prompt followed by what was
    served, padded; the token served at step i was read off position
    ``start + i``. Returns, for the ``n_valid`` served tokens (the rest 0):
    the gap by which each lies under the float32 reference's best logit
    there, and whether it is the reference's first choice. With
    ``control`` (``"int8"`` or ``"fp8"``) the tokens judged are instead the
    ones that forward puts first at the same positions."""
    def rows(lowp):
        h = hidden(w, tokens, n_head, eps, lowp)[0]
        h = jax.lax.dynamic_slice_in_dim(h, start, n_max, 0)
        return _head(w, h, lowp)                       # (n_max, V)

    logits = rows(None)
    if control:
        served = jnp.argmax(rows(control), -1).astype(served.dtype)
    best = jnp.max(logits, -1)
    mine = jnp.take_along_axis(logits, served[:, None], -1)[:, 0]
    live = jnp.arange(n_max) < n_valid
    gaps = jnp.where(live, best - mine, 0.0)
    hits = jnp.where(live, jnp.argmax(logits, -1) == served, False)
    return gaps, hits


def served_gaps(model, w, prompt, served, n_max, control=None):
    """One request as it was served: ``prompt`` ids and the ``served``
    tokens (at most ``n_max``). Returns, per served token, its gap under
    the float32 reference's best logit at its position and whether it is
    the reference's first choice (with ``control`` the tokens judged are
    the ones that forward puts first there).

    GPT-2 has a table of ``n_positions`` learned positions: the request is
    padded to a row of that length (one compiled program whatever the
    request), and the logits are read off a window of ``n_max`` positions
    that starts at the prompt's last. Where the prompt is so long that the
    window would pass the last position it starts earlier and the served
    tokens sit ``shift`` rows in."""
    s = W.sizes(model)
    p, n = len(prompt), len(served)
    row = np.zeros((1, s["P"]), np.int32)
    row[0, :p] = prompt
    row[0, p:p + n] = served
    padded = np.zeros((n_max,), np.int32)
    padded[:n] = served
    start = min(p - 1, s["P"] - n_max)
    shift = (p - 1) - start
    padded = np.roll(padded, shift)
    g, h = _served_gaps(
        w, jnp.asarray(row), jnp.int32(start), jnp.asarray(padded),
        jnp.int32(n + shift), n_head=s["H"],
        eps=float(model["layer_norm_epsilon"]), n_max=n_max,
        control=control)
    return np.asarray(g)[shift:shift + n], np.asarray(h)[shift:shift + n]


# ---------------------------------------------------------------------------
# training: loss and gradients (Adam's steps are chipbench/adam.py)
# ---------------------------------------------------------------------------
def loss_fn(w, x, y, n_head, eps, lowp=None):
    """Mean next-token cross entropy over every position of (B, T)."""
    h = hidden(w, x, n_head, eps, lowp)

    @jax.checkpoint
    def row(hy):
        lg = _head(w, hy[0], lowp)
        lse = jax.nn.logsumexp(lg, -1)
        return jnp.sum(lse - jnp.take_along_axis(
            lg, hy[1][:, None], -1)[:, 0])

    return jnp.sum(jax.lax.map(row, (h, y))) / y.size


@functools.partial(jax.jit, static_argnames=("n_head", "eps", "lowp"))
def _loss_and_grads(w, x, y, *, n_head, eps, lowp=None):
    return jax.value_and_grad(loss_fn)(w, x, y, n_head, eps, lowp)


@functools.partial(jax.jit, static_argnames=("n_head", "eps", "lowp"))
def _loss_only(w, x, y, *, n_head, eps, lowp=None):
    return loss_fn(w, x, y, n_head, eps, lowp)


def _statics(model, lowp):
    return dict(n_head=W.sizes(model)["H"],
                eps=float(model["layer_norm_epsilon"]), lowp=lowp)


def loss_and_grads(model, w, x, y, lowp=None):
    """The mean loss of the batch ``(x, y)`` and its gradient, a tree
    shaped like ``w``."""
    return _loss_and_grads(w, x, y, **_statics(model, lowp))


def loss_only(model, w, x, y, lowp=None):
    return _loss_only(w, x, y, **_statics(model, lowp))


def leaf_norms(tree):
    """Per program leaf: the L2 norm of each layer's slice of a stacked
    leaf, and of each top leaf. ``{name: (L,) or ()}``."""
    return {n: jnp.sqrt(jnp.sum(
        jnp.square(a), axis=tuple(range(1, a.ndim))
        if n in W.LAYER_NAMES else None)) for n, a in tree.items()}


def leaf_index(model):
    """``{program leaf name: (name in the weights' tree, layer or None)}``:
    how a per-leaf reading of the program lines up with ``leaf_norms``."""
    return W.program_leaf_index(W.sizes(model)["L"])
