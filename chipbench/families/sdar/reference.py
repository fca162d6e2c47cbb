"""The plain reference of the ``sdar`` family: ``jax.numpy`` float32, every
product at ``Precision.HIGHEST``, no cache, no kernel, every expert as a
plain SwiGLU under its gate. It imports nothing of the program.

What it computes (``N`` an RMSNorm with its own gain, ``R`` rotary
positions over the whole head in the half-split convention). A layer on
rows ``x``::

    q_h = R(N_q((N(x) W_q)_h)),  k_g = R(N_k((N(x) W_k)_g)),  v_g = (N(x) W_v)_g
    h   = x + concat_h(softmax(q_h k_{h // (H / Hkv)}^T / sqrt(dh)) v) W_o
    out = h + sum_{i in top K of pi} g_i E_i(N(h)),   pi = softmax(N(h) W_r)
    g_i = pi_i / sum_{j in top K} pi_j,   E(z) = (silu(z W_gate) * (z W_up)) W_down

under the block-causal mask (query ``i`` sees key ``j`` iff ``floor(j / B)
<= floor(i / B)``), then ``N(x) W_head``.

**Generation**, as the configuration's ``model`` group states it. The
prompt's whole blocks are given; each later block starts with the prompt's
tail (first block only) and the mask token elsewhere; a step runs the
block's rows against the prefix and itself, and unmasks the ``k_s`` masked
positions of highest confidence (the softmax probability of the argmax;
ties to the lower position), ``k_s`` from ``block_schedule``; a finished
block's keys are those of its final tokens.

``generate`` is that, literally: one forward of the whole sequence a step.
``served_gaps`` replays a SERVED request in ``steps + 1`` forwards: the
prefix never sees a later block, so one forward of the final sequence under
the mask gives every block's prefix keys at once, and one batched forward
a step index runs every block's rows against those keys and their own. The
replay ranks by ITS OWN confidence, teacher-forced: a position it unmasks
takes the served token as context for the later steps (or its own argmax
where nothing was served: the last block past the budget). A served token
is judged at the step the replay unmasks it: its gap under the replay's
best logit there, and whether it is the replay's argmax. The tests hold
the two forms to each other.

**A request reads the MEAN of its served tokens' gaps** (the limits file
gives the readings): a ranking whose two sides lie within a bfloat16
program's noise of each other, or a router whose eighth and ninth expert
do, falls the other way in a correct bfloat16 program; the program then
unmasks (or routes) otherwise, and its token meets another context than
the replay's, a few tenths of a logit away at that position. Such flips
are rare and scattered, so the widest single gap reads them and not the
precision, while a request's mean reads how far the program lies from
the reference over all its tokens, as the ``nemotronh`` cell's does. The
replay also reports, per position, the ranking margins of its block up to
its step (the log-confidence of the last position unmasked over the first
one left) and its row's narrowest router margin (the eighth router logit
over the ninth, over the layers), which ``scripts/sdar_chip_check.py``
reads beside the gaps.

``control`` (``"int8"`` / ``"fp8"``) computes the replay with the operands
of every dense product and the K/V rounded to that precision (the router,
which the program keeps float32, stays so) and its own ranking; the tokens
judged are the ones that replay puts first where it unmasks. ``FAULTS`` are
controls of another kind, the float32 replay with one mechanism broken:
``causal_mask`` a causal mask in place of the block-causal one;
``masked_commit`` every block's prefix keys computed from its state at its
last masked step, not from its final tokens; ``gates_unnormed`` the top-K
probabilities not renormalised; ``unmask_by_position`` the leftmost masked
positions unmasked in place of the most confident.
"""
from __future__ import annotations

import functools
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

HI = lax.Precision.HIGHEST
FAULTS = ("causal_mask", "masked_commit", "gates_unnormed",
          "unmask_by_position")
NEG = -1e30

#: a request is padded to a multiple of this many positions: the longest
#: context the family's cell serves, so that every request of it runs one
#: compiled shape. Short requests (tests) pad to a multiple of 64. A
#: step's rows (a block's B a block) are padded to a multiple of ROWS.
PAD_LONG, PAD_SHORT, ROWS = 3072, 64, 256

def block_schedule(masked, steps):
    """LLaDA's ``get_num_transfer_tokens``: ``masked`` positions over
    ``steps`` steps, the remainder to the first; steps of none dropped."""
    base, extra = divmod(int(masked), int(steps))
    return [k for k in (base + (i < extra) for i in range(int(steps))) if k]


def _fq(x, lowp):
    """Round ``x`` to ``lowp`` along its last axis."""
    if lowp == "int8":
        scale = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 127.0
        scale = jnp.where(scale > 0, scale, 1.0)
        return jnp.clip(jnp.round(x / scale), -127, 127) * scale
    if lowp == "fp8":
        m, e = jnp.frexp(x)                   # m in [0.5, 1): 4 bits kept
        return jnp.ldexp(jnp.round(m * 16.0) / 16.0, e)
    raise ValueError(f"no control precision {lowp!r}")


def _dense(x, w, lowp):
    if lowp:
        x, w = _fq(x, lowp), _fq(w, lowp)
    return jnp.dot(x, w, precision=HI)


def _rms(x, g, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                         + eps) * g


def _rope(x, pos, theta):
    """Half-split rotary over the whole last axis of ``x`` (T, h, d)."""
    d = x.shape[-1]
    half = d // 2
    inv = jnp.exp(-np.log(theta) * (jnp.arange(half, dtype=jnp.float32)
                                    * 2.0 / d))
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _qkv(s, lw, x, pos, lowp):
    t, dh = x.shape[0], s["dh"]
    z = _rms(x, lw["attn_norm"], s["eps"])
    q = _dense(z, lw["w_q"], lowp).reshape(t, s["H"], dh)
    k = _dense(z, lw["w_k"], lowp).reshape(t, s["Hkv"], dh)
    v = _dense(z, lw["w_v"], lowp).reshape(t, s["Hkv"], dh)
    q = _rope(_rms(q, lw["q_norm"], s["eps"]), pos, s["theta"])
    k = _rope(_rms(k, lw["k_norm"], s["eps"]), pos, s["theta"])
    if lowp:                                 # what a cache would hold
        k, v = _fq(k, lowp), _fq(v, lowp)
    return q, k, v


def _route(s, lw, z, fault):
    """``ids`` (T, K), gates (T, K), and the router margin (the K-th
    logit less the next), float32 and never rounded."""
    logits = jnp.dot(z, lw["router"], precision=HI)
    top, ids = lax.top_k(logits, s["K"] + 1)
    probs = jax.nn.softmax(logits, -1)
    gates = jnp.take_along_axis(probs, ids[:, :s["K"]], -1)
    if s["norm_topk"] and fault != "gates_unnormed":
        gates = gates / jnp.sum(gates, -1, keepdims=True)
    return ids[:, :s["K"]], gates, top[:, -2] - top[:, -1]


def _moe(s, lw, x, lowp, fault):
    """``x + sum_chosen g_i E_i(N(x))``: a loop over the experts, each over
    every row, weighted by its gate (0 where it was not chosen); and the
    router margin of each row."""
    z = _rms(x, lw["ffn_norm"], s["eps"])
    ids, gates, margin = _route(s, lw, z, fault)

    def expert(acc, ew):
        e, wg, wu, wd = ew
        g_e = jnp.sum(jnp.where(ids == e, gates, 0.0), -1)
        h = jax.nn.silu(_dense(z, wg, lowp)) * _dense(z, wu, lowp)
        return acc + g_e[:, None] * _dense(h, wd, lowp), None

    out = lax.scan(expert, x, (jnp.arange(s["E"]), lw["e_gate"], lw["e_up"],
                               lw["e_down"]))[0]
    return out, margin


def _heads(s, q, keys, values, valid):
    """Attention of ``q`` (T, H, dh) over ``keys`` / ``values`` (S, Hkv,
    dh) where ``valid`` (T, S): one head at a time."""
    rep = s["H"] // s["Hkv"]
    scale = 1.0 / np.sqrt(s["dh"])

    def head(args):
        qh, h = args
        g = h // rep
        sc = jnp.dot(qh, keys[:, g].T, precision=HI) * scale
        p = jax.nn.softmax(jnp.where(valid, sc, NEG), axis=-1)
        return jnp.dot(p, values[:, g], precision=HI)

    o = lax.map(head, (jnp.moveaxis(q, 1, 0), jnp.arange(s["H"])))
    return jnp.moveaxis(o, 0, 1).reshape(q.shape[0], -1)


def _sees(s, qpos, kpos, fault):
    if fault == "causal_mask":
        return kpos[None, :] <= qpos[:, None]
    return kpos[None, :] // s["B"] <= qpos[:, None] // s["B"]


@functools.lru_cache(maxsize=None)
def _seq_layer(sizes_json, control):
    """One layer over a whole sequence: ``(x, k, v)``."""
    s = json.loads(sizes_json)
    lowp, fault = (None, control) if control in FAULTS else (control, None)

    def run(lw, x):
        pos = jnp.arange(x.shape[0], dtype=jnp.int32)
        q, k, v = _qkv(s, lw, x, pos, lowp)
        x = x + _dense(_heads(s, q, k, v, _sees(s, pos, pos, fault)),
                       lw["w_o"], lowp)
        return _moe(s, lw, x, lowp, fault)[0], k, v

    return jax.jit(run)


@functools.lru_cache(maxsize=None)
def _rows_layer(sizes_json, control):
    """One layer over blocks' rows ``x`` (R, D), R a multiple of ``B``,
    each block against the prefix keys ``k0``/``v0`` (T, Hkv, dh) before
    its own start and against its own rows: ``(x, router margin)``."""
    s = json.loads(sizes_json)
    lowp, fault = (None, control) if control in FAULTS else (control, None)
    b = s["B"]

    def run(lw, x, pos, k0, v0):
        r = x.shape[0]
        q, k, v = _qkv(s, lw, x, pos, lowp)
        start = pos - pos % b
        kpos = jnp.arange(k0.shape[0], dtype=jnp.int32)
        # a row's keys: the prefix, then its block's own B rows
        own = jnp.arange(r, dtype=jnp.int32).reshape(-1, b)
        own_rows = jnp.repeat(own, b, axis=0)                   # (R, B)
        rep = s["H"] // s["Hkv"]
        scale = 1.0 / np.sqrt(s["dh"])
        pre_ok = kpos[None, :] < start[:, None]
        own_ok = _sees(s, pos, pos, fault)[
            jnp.arange(r)[:, None], own_rows]                   # (R, B)

        def head(args):
            qh, h = args
            g = h // rep
            s0 = jnp.dot(qh, k0[:, g].T, precision=HI) * scale
            s1 = jnp.sum(qh[:, None, :] * k[own_rows, g], -1) * scale
            sc = jnp.concatenate([jnp.where(pre_ok, s0, NEG),
                                  jnp.where(own_ok, s1, NEG)], -1)
            p = jax.nn.softmax(sc, axis=-1)
            t = k0.shape[0]
            return jnp.dot(p[:, :t], v0[:, g], precision=HI) + jnp.sum(
                p[:, t:, None] * v[own_rows, g], 1)

        o = lax.map(head, (jnp.moveaxis(q, 1, 0), jnp.arange(s["H"])))
        o = jnp.moveaxis(o, 0, 1).reshape(r, -1)
        x = x + _dense(o, lw["w_o"], lowp)
        return _moe(s, lw, x, lowp, fault)

    return jax.jit(run)


@functools.lru_cache(maxsize=None)
def _head_fn(sizes_json, lowp):
    """Per row: the best logit, the log-confidence (best less logsumexp),
    the argmax and the logit of ``target`` (-1: none)."""
    s = json.loads(sizes_json)

    def run(top, x, target):
        logits = _dense(_rms(x, top["final_norm"], s["eps"]), top["head"],
                        lowp)
        best = jnp.max(logits, -1)
        tl = jnp.take_along_axis(logits, jnp.maximum(target, 0)[:, None],
                                 -1)[:, 0]
        return (best, best - jax.nn.logsumexp(logits, -1),
                jnp.argmax(logits, -1).astype(jnp.int32), tl)

    return jax.jit(run)


def _key(weights):
    return json.dumps(weights.s, sort_keys=True)


def _embed(top, ids):
    return jnp.take(top["embed"], jnp.asarray(ids, jnp.int32), axis=0)


def forward(weights, tokens, control=None):
    """Logits (T, V) of ``tokens`` (T,) under the mask: the whole forward
    (the tests hold the program's ``forward`` to it)."""
    key = _key(weights)
    lowp = None if control in FAULTS else control
    top = weights.top()
    x = _embed(top, tokens)
    for i in range(weights.s["L"]):
        x, _, _ = _seq_layer(key, control)(weights.layer(i), x)
    z = _rms(x, top["final_norm"], weights.s["eps"])
    return _dense(z, top["head"], lowp)


def _prefix_kv(weights, seq, control):
    """Every layer's keys and values of ``seq`` under the mask."""
    key = _key(weights)
    x = _embed(weights.top(), seq)
    kv = []
    for i in range(weights.s["L"]):
        x, k, v = _seq_layer(key, control)(weights.layer(i), x)
        kv.append((k, v))
    return kv


def _rows(weights, kv, ids, pos, target, control):
    """One batched step: every block's rows ``ids`` at ``pos`` against the
    prefix keys ``kv`` and their own rows. Per row: best logit,
    log-confidence, argmax, the logit of ``target``, router margin."""
    key = _key(weights)
    lowp = None if control in FAULTS else control
    top = weights.top()
    x = _embed(top, ids)
    margin = None
    for i in range(weights.s["L"]):
        x, m = _rows_layer(key, control)(
            weights.layer(i), x, jnp.asarray(pos, jnp.int32), *kv[i])
        margin = m if margin is None else jnp.minimum(margin, m)
    out = _head_fn(key, lowp)(top, x, jnp.asarray(target, jnp.int32))
    return [np.asarray(a) for a in out] + [np.asarray(margin)]


def replay(weights, prompt, served, t_pad, control=None, judge=None):
    """The served request replayed, batched (the module's text),
    teacher-forced by ``served``. Returns per position from the prompt's
    first block on (``pos``): whether it holds a served token
    (``judged``), the step it was unmasked at, and at that step the best
    logit, the logit of its token in ``judge`` (default: the served one),
    the argmax, the narrowest ranking margin of its block up to that step
    and its row's router margin; and ``last_masked``, the sequence with
    each block as its last step found it."""
    s = weights.s
    b, steps, mask = s["B"], s["steps"], s["mask"]
    p, n = len(prompt), len(served)
    seq = np.zeros((t_pad,), np.int32)
    seq[:p] = prompt
    seq[p:p + n] = served
    first, end = p - p % b, -(-(p + n) // b) * b
    pos = np.arange(first, end, dtype=np.int32)
    r = len(pos)
    # rows past the last block (at position 0: no prefix) keep every
    # step's compiled shape the same over requests; nothing reads them
    pad = -(-r // ROWS) * ROWS - r
    known = pos < p + n
    target = np.where(known, seq[np.minimum(pos, t_pad - 1)], -1)
    if judge is not None:
        judged_seq = seq.copy()
        judged_seq[p:p + n] = judge
        judge = np.where(known, judged_seq[np.minimum(pos, t_pad - 1)], -1)
    else:
        judge = target
    fill = pos < p
    ctx = np.where(fill, seq[pos], 0)
    out = {k: np.zeros((r,), dt) for k, dt in (
        ("step", np.int32), ("best", np.float32), ("mine", np.float32),
        ("argmax", np.int32), ("rank_margin", np.float32),
        ("route_margin", np.float32))}
    out["step"][:] = -1
    blk_margin = np.full((r // b,), np.inf, np.float32)
    # the prefix keys: the final sequence, or under ``masked_commit`` each
    # block as its last masked step left it (from the float32 ranking)
    commit_seq = seq
    if control == "masked_commit":
        commit_seq = replay(weights, prompt, served, t_pad)["last_masked"]
    kv = _prefix_kv(weights, commit_seq, control)
    sched = [block_schedule(b - int(fill[i * b:(i + 1) * b].sum()), steps)
             for i in range(r // b)]
    last_masked = seq.copy()
    for st in range(steps):
        ids = np.where(fill, ctx, mask)
        best, conf, amax, mine, rmarg = _rows(
            weights, kv, np.pad(ids, (0, pad)), np.pad(pos, (0, pad)),
            np.pad(judge, (0, pad), constant_values=-1), control)
        for i in range(r // b):
            if st >= len(sched[i]):
                continue
            lo = i * b
            if st == len(sched[i]) - 1:
                last_masked[first + lo:first + lo + b] = ids[lo:lo + b]
            open_ = [j for j in range(lo, lo + b) if not fill[j]]
            if control == "unmask_by_position":
                order = open_
            else:
                order = sorted(open_, key=lambda j: (-conf[j], j))
            k = sched[i][st]
            if k < len(order):
                blk_margin[i] = min(blk_margin[i],
                                    conf[order[k - 1]] - conf[order[k]])
            for j in order[:k]:
                out["step"][j] = st
                out["best"][j], out["mine"][j] = best[j], mine[j]
                out["argmax"][j] = amax[j]
                out["rank_margin"][j] = blk_margin[i]
                out["route_margin"][j] = rmarg[j]
                fill[j] = True
                ctx[j] = target[j] if known[j] else amax[j]
    out["pos"], out["judged"] = pos, known & ~(pos < p)
    out["last_masked"] = last_masked
    return out


def generate(weights, prompt, n_new, control=None):
    """The literal generation: one forward of the whole sequence (prefix
    and current block) a step, the block's rows ranked and unmasked, the
    prefix the final tokens. Returns the ``n_new`` tokens after the
    prompt. ``unmask_by_position`` and the precisions apply; a
    ``masked_commit`` sequence keeps each block as its last masked step
    left it in the forwards of later blocks."""
    s = weights.s
    b, steps, mask = s["B"], s["steps"], s["mask"]
    p = len(prompt)
    final = list(prompt)
    seen = list(prompt)        # what later blocks' forwards are fed
    start = p - p % b
    while len(final) < p + n_new:
        tail = final[start:]
        cur = tail + [mask] * (b - len(tail))
        fill = [True] * len(tail) + [False] * (b - len(tail))
        stale = None
        for k in block_schedule(b - len(tail), steps):
            stale = [c if f else mask for c, f in zip(cur, fill)]
            ids = seen[:start] + stale
            lg = np.asarray(forward(weights, np.asarray(ids, np.int32),
                                    control))[start:start + b]
            conf = lg.max(-1) - np.asarray(jax.nn.logsumexp(lg, -1))
            open_ = [j for j in range(b) if not fill[j]]
            order = open_ if control == "unmask_by_position" else sorted(
                open_, key=lambda j: (-conf[j], j))
            for j in order[:k]:
                cur[j], fill[j] = int(lg[j].argmax()), True
        final = final[:start] + cur
        seen = seen[:start] + (stale if control == "masked_commit"
                               else cur)
        start += b
    return np.asarray(final[p:p + n_new], np.int32)


def served_gaps(model, weights, prompt, served, n_max, control=None):
    """One request as it was served: ``prompt`` ids and the ``served``
    tokens (at most ``n_max``). Returns, per served token, the mean over
    the request of each token's gap under the float32 replay's best logit
    at the step the replay unmasks it (the module's text says why the
    mean), and whether the token is the replay's argmax there; with
    ``control`` (a precision, or one of ``FAULTS``) the tokens judged are
    the ones that control's own replay puts first where it unmasks. The
    sequence is padded to a multiple of ``PAD_LONG`` positions, or of
    ``PAD_SHORT`` where it is short."""
    del model
    t0 = time.perf_counter()
    prompt = np.asarray(prompt, np.int32)
    served = np.asarray(served, np.int32)
    p, n = len(prompt), len(served)
    b = weights.s["B"]
    q = PAD_LONG if p + n_max > PAD_LONG // 4 else PAD_SHORT
    t_pad = -(-(-(-(p + n) // b) * b) // q) * q
    tokens = served
    if control:
        got = replay(weights, prompt, served, t_pad, control)
        tokens = got["argmax"][got["judged"]][:n]
    ref = replay(weights, prompt, served, t_pad, judge=tokens)
    sel = ref["judged"]
    every = ref["best"][sel][:n] - ref["mine"][sel][:n]
    hits = ref["argmax"][sel][:n] == tokens
    print(f"chipbench sdar reference: {p} + {n} tokens padded to {t_pad}, "
          f"control {control}: mean gap {every.mean():.4f}, widest "
          f"{every.max():.4f}, {time.perf_counter() - t0:.1f} s",
          file=sys.stderr, flush=True)
    return np.full((n,), every.mean()), hits


def _not_trained(*_a, **_k):
    raise SystemExit("chipbench: the sdar family is served, not trained: "
                     "its weights come a layer at a time "
                     "(chipbench/README.md, A model family)")


loss_and_grads = loss_only = leaf_norms = leaf_index = _not_trained
