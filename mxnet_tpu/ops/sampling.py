"""Sampling heads for the serving stack: temperature / top-k / top-p
logit warping, per-row categorical sampling with EXPLICIT PRNG keys,
and the speculative-decoding accept rule.

Design constraints (serving/generate.py is the caller):

- Every function is a pure jnp program over FIXED shapes — the engine
  jits each one once per shape at ``warmup()`` and the steady state
  compiles nothing. Per-request knobs (``temperature``/``top_k``/
  ``top_p``) are RUNTIME ``(B,)`` vectors, one entry per slot, so a
  mixed batch of greedy and stochastic requests runs the same program.
- Randomness is an explicit per-row key (raw ``(B, 2)`` uint32 PRNG
  key data — random_state.py's convention). Each call SPLITS every
  row's key inside the trace and returns the advanced keys; the engine
  threads them like it threads the KV cache. A request's key stream
  therefore depends only on its seed and the engine configuration —
  same-seed reruns are bitwise-reproducible across engine restarts,
  and co-tenants can never perturb a stream (rows are independent).
- ``temperature <= 0`` marks a GREEDY row: the sampled paths are
  bypassed with ``argmax`` over the UNWARPED logits (bit-equal to the
  engine's host-side greedy argmax), so greedy requests riding in a
  sampling batch stay token-identical to a pure-greedy engine.

The warp order is the conventional one (HF ``LogitsProcessor`` chain):
temperature first, then top-k, then top-p over the renormalized
post-top-k distribution. ``top_k <= 0`` (or >= vocab) and
``top_p >= 1`` disable their filters.

``speculative_accept`` implements both acceptance disciplines of
docs/SERVING.md "Speculative decoding":

- greedy rows: accept draft token ``d_{j+1}`` while it equals the
  target's argmax ``t_j``, then commit the target's own token at the
  first mismatch (or the bonus token after k accepts) — the committed
  stream is EXACTLY what non-speculative greedy decode would emit.
- stochastic rows: the standard speculative-sampling rule (Leviathan
  et al. 2023; Chen et al. 2023): accept ``d`` with probability
  ``min(1, p(d)/q(d))`` where ``p``/``q`` are the WARPED target/draft
  distributions, and on rejection sample from the residual
  ``norm(max(p - q, 0))`` — the marginal distribution of every
  committed token is exactly the warped target distribution.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

#: the attention convention's finite -inf (ops/attention.py NEG_INF):
#: masked logits must survive softmax without minting NaNs
NEG_INF = -1e30

__all__ = ["warp_logits", "sample_tokens", "greedy_tokens",
           "carry_tokens", "sample_with_probs", "greedy_accept", "speculative_accept"]


def warp_logits(logits, temperature, top_k, top_p):
    """Apply temperature, then top-k, then top-p to ``logits``
    (..., V). The knobs broadcast over the leading axes (the serving
    engine passes ``(B,)`` vectors against ``(B, V)`` logits, and the
    accept rule ``(B, 1)`` against ``(B, K+1, V)``). Masked entries
    are set to ``NEG_INF``; at least one entry per row always
    survives. ``temperature <= 0`` rows are warped at temperature 1 —
    the caller treats them as greedy and never samples the result."""
    v = logits.shape[-1]
    temperature = jnp.asarray(temperature, jnp.float32)
    top_k = jnp.asarray(top_k, jnp.int32)
    top_p = jnp.asarray(top_p, jnp.float32)
    t = jnp.where(temperature > 0, temperature, 1.0)[..., None]
    x = logits.astype(jnp.float32) / t
    # top-k: keep the k largest (k <= 0 or >= V disables)
    desc = jnp.sort(x, axis=-1)[..., ::-1]
    k_eff = jnp.clip(top_k, 1, v)
    kth = jnp.take_along_axis(
        desc, jnp.broadcast_to(k_eff - 1, x.shape[:-1])[..., None],
        axis=-1)
    k_on = (top_k > 0) & (top_k < v)
    x = jnp.where(k_on[..., None] & (x < kth), NEG_INF, x)
    # top-p: smallest prefix of the (post-top-k) sorted distribution
    # whose mass reaches p; a token is kept iff the mass BEFORE it is
    # still below p, so the head token always survives
    probs = jax.nn.softmax(x, axis=-1)
    order = jnp.argsort(-probs, axis=-1)
    ps = jnp.take_along_axis(probs, order, axis=-1)
    cum = jnp.cumsum(ps, axis=-1)
    p_on = (top_p > 0) & (top_p < 1.0)
    keep_sorted = ((cum - ps) < jnp.clip(top_p, 0.0, 1.0)[..., None]) \
        | ~p_on[..., None]
    inv = jnp.argsort(order, axis=-1)
    keep = jnp.take_along_axis(keep_sorted, inv, axis=-1)
    return jnp.where(keep, x, NEG_INF)


def _split_rows(keys):
    """Split every row's key: ``(B, 2)`` uint32 -> (advanced keys,
    subkeys), both ``(B, 2)``."""
    nk = jax.vmap(jax.random.split)(jnp.asarray(keys, jnp.uint32))
    return nk[:, 0], nk[:, 1]


def sample_tokens(keys, logits, temperature, top_k, top_p):
    """One sampling step over a row batch: warp ``logits`` (B, V) with
    each row's knobs and draw one token per row with its own subkey.
    Greedy rows (``temperature <= 0``) take ``argmax`` of the RAW
    logits instead (bit-equal to host-side greedy). Returns
    ``(tokens (B,) int32, advanced keys (B, 2))`` — thread the keys
    into the next call."""
    greedy = jnp.asarray(temperature, jnp.float32) <= 0
    w = warp_logits(logits, temperature, top_k, top_p)
    new_keys, sub = _split_rows(keys)
    sampled = jax.vmap(jax.random.categorical)(sub, w)
    tok = jnp.where(greedy, jnp.argmax(logits, axis=-1), sampled)
    return tok.astype(jnp.int32), new_keys


def greedy_tokens(logits):
    """The greedy rows of ``sample_tokens`` alone: ``argmax`` of the RAW
    logits (B, V) -> (B,) int32, the first of equal maxima as the host's
    argmax takes it. What an all-greedy engine's tick runs, so that
    (B,) ints and not (B, V) floats come to the host."""
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


def carry_tokens(picked, fresh, use_fresh):
    """A tick's input tokens (B,) int32 when the tick before it is still
    in flight: row ``i`` is ``fresh[i]`` (from the host: a row that
    entered decode since) where ``use_fresh[i]``, else ``picked[i]``, the
    earlier tick's pick where it lies on the device."""
    return jnp.where(use_fresh, fresh, picked).astype(jnp.int32)


def sample_with_probs(keys, logits, temperature, top_k, top_p):
    """``sample_tokens`` that also returns the full WARPED probability
    rows (B, V) the tokens were drawn from — the draft-model step of
    speculative decoding, whose ``q`` distribution the accept rule
    needs (both the proposed token's probability and the full residual
    ``max(p - q, 0)``). Greedy rows' probabilities are returned but
    unused (the greedy accept rule compares argmaxes)."""
    greedy = jnp.asarray(temperature, jnp.float32) <= 0
    w = warp_logits(logits, temperature, top_k, top_p)
    probs = jax.nn.softmax(w, axis=-1)
    new_keys, sub = _split_rows(keys)
    sampled = jax.vmap(jax.random.categorical)(sub, w)
    tok = jnp.where(greedy, jnp.argmax(logits, axis=-1), sampled)
    return tok.astype(jnp.int32), probs, new_keys


def greedy_accept(target_logits, draft_tokens):
    """The GREEDY accept rule alone: accept draft token ``d_{j+1}``
    while it equals the target argmax ``t_j``, commit the target's
    token at the cut. Returns ``(commit (B, K+1) int32, n_commit
    (B,) int32)`` — the committed stream is exactly non-speculative
    greedy decode's. This is ``speculative_accept`` restricted to
    ``temperature <= 0`` rows, WITHOUT the stochastic machinery (the
    sorts and the categorical draws cost more than the whole verify
    matmul at small models — an all-greedy engine iteration must not
    pay for them)."""
    b, k1, _v = target_logits.shape
    k = k1 - 1
    draft_tokens = jnp.asarray(draft_tokens, jnp.int32)
    tgt = jnp.argmax(target_logits, axis=-1).astype(jnp.int32)
    acc = (draft_tokens == tgt[:, :k]).astype(jnp.int32)
    n_acc = jnp.cumprod(acc, axis=-1).sum(axis=-1)
    cut = jnp.take_along_axis(tgt, n_acc[:, None], axis=1)[:, 0]
    j = jnp.arange(k1, dtype=jnp.int32)[None, :]
    d_pad = jnp.concatenate(
        [draft_tokens, jnp.zeros((b, 1), jnp.int32)], axis=1)
    commit = jnp.where(j < n_acc[:, None], d_pad,
                       jnp.where(j == n_acc[:, None], cut[:, None], 0))
    return commit.astype(jnp.int32), (n_acc + 1).astype(jnp.int32)


def speculative_accept(keys, target_logits, draft_tokens, draft_probs,
                       temperature, top_k, top_p):
    """The speculative-decoding accept rule over one verify step.

    ``target_logits`` (B, K+1, V) are the target model's logits at the
    K+1 verified positions (position j predicts the token AFTER the
    j-th verified input, i.e. after ``[last, d_1 .. d_j]``);
    ``draft_tokens`` (B, K) are the draft's proposals ``d_1 .. d_K``;
    ``draft_probs`` (B, K, V) the WARPED draft distributions each was
    drawn from (``sample_with_probs``). Knobs are per-row ``(B,)``.

    Returns ``(commit (B, K+1) int32, n_commit (B,) int32, advanced
    keys)``: row b commits ``commit[b, :n_commit[b]]`` — the accepted
    draft prefix plus exactly one target-derived token (the argmax /
    residual sample at the first rejection, or the bonus token after a
    full accept). ``1 <= n_commit <= K+1`` always: every verify step
    commits at least the token non-speculative decode would have."""
    b, k1, v = target_logits.shape
    k = k1 - 1
    temperature = jnp.asarray(temperature, jnp.float32)
    greedy = temperature <= 0
    draft_tokens = jnp.asarray(draft_tokens, jnp.int32)

    # greedy rule: accept while draft argmax == target argmax, then
    # take the target's token — exactly non-speculative greedy output
    tgt = jnp.argmax(target_logits, axis=-1).astype(jnp.int32)  # (B,K1)
    acc_g = draft_tokens == tgt[:, :k]

    # stochastic rule on the warped target distribution
    w = warp_logits(target_logits, temperature[:, None],
                    jnp.asarray(top_k, jnp.int32)[:, None],
                    jnp.asarray(top_p, jnp.float32)[:, None])
    p = jax.nn.softmax(w, axis=-1)                               # (B,K1,V)
    new_keys, sub = _split_rows(keys)
    u = jax.vmap(
        lambda kk: jax.random.uniform(jax.random.fold_in(kk, 0), (k,))
    )(sub) if k else jnp.zeros((b, 0), jnp.float32)
    p_d = jnp.take_along_axis(p[:, :k], draft_tokens[..., None],
                              axis=-1)[..., 0]                   # (B,K)
    q_d = jnp.take_along_axis(draft_probs, draft_tokens[..., None],
                              axis=-1)[..., 0]
    acc_s = u <= p_d / jnp.maximum(q_d, 1e-20)   # u < min(1, p/q)

    acc = jnp.where(greedy[:, None], acc_g, acc_s)
    n_acc = jnp.cumprod(acc.astype(jnp.int32), axis=-1).sum(axis=-1)

    # the token at the cut position: target argmax (greedy) or a
    # sample from the residual norm(max(p - q, 0)); after a full
    # accept the "residual" at the bonus position is p itself (q = 0)
    idx = jnp.broadcast_to(n_acc[:, None, None], (b, 1, v))
    p_cut = jnp.take_along_axis(p, idx, axis=1)[:, 0]            # (B,V)
    q_pad = jnp.concatenate(
        [draft_probs, jnp.zeros((b, 1, v), draft_probs.dtype)], axis=1)
    q_cut = jnp.take_along_axis(q_pad, idx, axis=1)[:, 0]
    resid = jnp.maximum(p_cut - q_cut, 0.0)
    rs = resid.sum(axis=-1, keepdims=True)
    # a numerically-empty residual (p == q to the last ulp) means the
    # rejection had probability ~0 — fall back to p rather than NaN
    dist = jnp.where(rs > 1e-20, resid / jnp.maximum(rs, 1e-20), p_cut)
    cut_s = jax.vmap(
        lambda kk, d: jax.random.categorical(
            jax.random.fold_in(kk, 1),
            jnp.log(jnp.maximum(d, 1e-38))))(sub, dist)
    cut_g = jnp.take_along_axis(tgt, n_acc[:, None], axis=1)[:, 0]
    cut = jnp.where(greedy, cut_g, cut_s).astype(jnp.int32)

    j = jnp.arange(k1, dtype=jnp.int32)[None, :]
    d_pad = jnp.concatenate(
        [draft_tokens, jnp.zeros((b, 1), jnp.int32)], axis=1)
    commit = jnp.where(j < n_acc[:, None], d_pad,
                       jnp.where(j == n_acc[:, None], cut[:, None], 0))
    return (commit.astype(jnp.int32), (n_acc + 1).astype(jnp.int32),
            new_keys)
