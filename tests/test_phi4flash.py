"""The ``phi4flash`` decoder family at a toy size that keeps every kind of
layer of the published model: 8 layers (two Mamba/window pairs, the Mamba
layer that keeps the memory, the full-attention layer that keeps the one
paged cache, one gated-memory/cross pair), 4 query over 2 key/value heads
of 16, a window (8) smaller than the longest prompt, chunks of 8, pages of
4, ``d_state`` 4.

The system (``Phi4FlashModel`` through ``GenerationEngine``: chunked
prefill of the self-decoder, the cross-decoder on a prompt's last row,
then decode through the pool, the rings and the recurrent states) is
compared with the plain float32 reference of
``chipbench/families/phi4flash/`` on the benchmark's seeded weights:
logits, not tokens. CPU, about a minute together.
"""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu import telemetry  # noqa: E402
from mxnet_tpu.gluon.model_zoo.phi4flash import Phi4FlashModel  # noqa: E402
from mxnet_tpu.ops import ssm  # noqa: E402
from mxnet_tpu.serving import GenerationEngine  # noqa: E402

from chipbench.families.phi4flash import costs as C  # noqa: E402
from chipbench.families.phi4flash import program as P  # noqa: E402
from chipbench.families.phi4flash import reference as R  # noqa: E402
from chipbench.families.phi4flash import weights as W  # noqa: E402

CHUNK, PAGE, S_MAX = 8, 4, 64
MODEL = dict(
    vocab_size=512, hidden_size=64, num_hidden_layers=8,
    num_attention_heads=4, num_key_value_heads=2, intermediate_size=160,
    sliding_window=8, mb_per_layer=2, layer_norm_eps=1e-5, d_state=4,
    d_conv=4, expand=2, dt_rank=4, initializer_range=0.2,
    prefill_chunk=CHUNK)
SEED = 31
#: float32 leaves against the float32 reference: rounding only (the CPU's
#: products against ``Precision.HIGHEST``, the order of a sum)
TOL32 = 2e-3
#: bfloat16 leaves, cache and activations over eight layers: a logit
#: moves by 0.05-0.31 (the median of a request's rows 0.09-0.13), and a
#: served token then lies under the reference's best by at most 0.06
TOL16, GAP16 = 0.4, 0.08


def build(dtype="bfloat16"):
    """The toy model as ``families/phi4flash/program.build_model`` builds
    the real one; ``float32`` installs the seeded weights unrounded in
    dtype (the values are those bfloat16 holds either way)."""
    return P.build_model(MODEL, SEED, dtype=dtype, max_length=S_MAX)


def engine(net, slots=2, **more):
    args = dict(max_slots=slots, max_length=S_MAX, paged=True,
                page_size=PAGE, prefill_chunk=CHUNK, prefix_cache=False,
                compute_dtype=net.generation_support["compute_dtype"][0],
                max_new_tokens=16)
    args.update(more)
    return GenerationEngine(net, **args)


class Spy:
    """Records what the engine's model calls return and how they were
    made: the last prefill chunk's row for a slot, then each decode
    tick's; every prefill call's ``(fresh, last)``."""

    def __init__(self, net):
        self.net, self.rows, self.calls = net, {}, []
        self._prefill, self._decode = net.prefill_paged, \
            net.decode_step_paged
        net.prefill_paged, net.decode_step_paged = self.prefill, self.decode

    def prefill(self, tokens, n_valid, slot, pages, cache, **kw):
        lg, cache = self._prefill(tokens, n_valid, slot, pages, cache, **kw)
        self.calls.append((bool(kw.get("fresh")), kw["last"]))
        self.rows[int(slot)] = [np.asarray(lg)[0]]
        return lg, cache

    def decode(self, tokens, active, cache):
        lg, cache = self._decode(tokens, active, cache)
        for b in np.flatnonzero(np.asarray(active)):
            self.rows[int(b)].append(np.asarray(lg)[b])
        return lg, cache

    def undo(self):
        self.net.prefill_paged, self.net.decode_step_paged = \
            self._prefill, self._decode


@pytest.fixture(scope="module")
def net():
    return build()


@pytest.fixture(scope="module")
def net32():
    return build("float32")


def serve(net, requests, slots=2):
    """Requests ``[(prompt, n_new)]`` through ``submit``, one after the
    other; returns each one's tokens and the logits row behind each."""
    spy = Spy(net)
    out = []
    try:
        with engine(net, slots=slots) as eng:
            for prompt, n_new in requests:
                spy.rows.clear()
                res = eng.submit(prompt, max_new_tokens=n_new).result(
                    timeout=300)
                rows = next(r for r in spy.rows.values()
                            if len(r) >= n_new)
                out.append((list(res.tokens), np.stack(rows[:n_new])))
    finally:
        spy.undo()
    return out, spy.calls


def reference_rows(prompt, tokens, control=None):
    seq = np.concatenate([prompt, tokens]).astype(np.int32)
    row = np.zeros((S_MAX,), np.int32)
    row[:len(seq)] = seq
    return np.asarray(R.logits_rows(
        MODEL, W.make(MODEL, SEED), row, len(prompt) - 1, len(tokens),
        control))


#: (prompt, new): one bucket padded (3 of 8), nothing wraps; a whole
#: bucket; two chunks, the second padded, decoding past the window; two
#: whole chunks; chunks that pass the window, a context that passes the
#: ring (16) twice over
SHAPES = [(3, 4), (8, 6), (11, 9), (16, 5), (29, 12), (41, 10)]


def _prompt(n):
    return np.random.default_rng(n).integers(
        0, MODEL["vocab_size"], n).astype(np.int32)


@pytest.fixture(scope="module")
def served32(net32):
    return serve(net32, [(_prompt(n), k) for n, k in SHAPES])[0]


@pytest.mark.parametrize("case", range(len(SHAPES)))
def test_engine_logits_follow_the_reference(served32, case):
    """Prefill in chunks (the self-decoder on every row, the
    cross-decoder on the last), then decode through the pool, the rings
    and the states, against the reference's one whole forward pass:
    every logit of every served position."""
    n_prompt, n_new = SHAPES[case]
    tokens, rows = served32[case]
    assert len(tokens) == n_new
    ref = reference_rows(_prompt(n_prompt), tokens)
    assert np.abs(rows - ref).max() <= TOL32
    assert tokens == [int(r.argmax()) for r in ref]


def test_the_shapes_cross_window_chunk_and_ring(net32):
    assert net32.ring_size == 16 and MODEL["sliding_window"] == 8
    assert SHAPES[0][0] < CHUNK and SHAPES[2][0] % CHUNK
    assert SHAPES[-1][0] + SHAPES[-1][1] > 3 * net32.ring_size


def test_bfloat16_follows_the_reference(net):
    """The model as it is served: bfloat16 leaves, pool, rings and
    tails; float32 states."""
    prompt = _prompt(29)
    ((tokens, rows),), _ = serve(net, [(prompt, 12)])
    ref = reference_rows(prompt, tokens)
    gap = np.abs(rows - ref).max(-1)
    assert gap.max() <= TOL16 and np.median(gap) <= TOL16 / 2, gap
    gaps, _ = R.served_gaps(MODEL, W.make(MODEL, SEED), prompt, tokens, 12)
    assert gaps.max() <= GAP16


def test_forward_equals_prefill_then_decode(net32, served32):
    n = SHAPES[4][0]
    tokens, rows = served32[4]
    seq = np.concatenate([_prompt(n), tokens]).astype(np.int32)
    whole = np.asarray(net32(mx.np.array(seq[None]))._data)[0]
    assert np.abs(whole[n - 1:n - 1 + len(tokens)] - rows).max() <= TOL32


# -- what a recurrence owes the engine -----------------------------------------
def _cache(net, slots=2):
    return net.init_paged_cache(slots, slots * (S_MAX // PAGE) + 1, PAGE,
                                S_MAX)


def _pages(slot):
    n = S_MAX // PAGE
    return np.arange(1 + slot * n, 1 + (slot + 1) * n, dtype=np.int32)


def _state(cache, slot):
    """The slot's recurrent states, tails and rings."""
    return {k: np.asarray(cache[k][:, slot], np.float32)
            for k in ("ssm", "conv", "ring_k", "ring_v")}


STATE_ROUTES = {
    # five valid rows of a bucket of eight, then three ticks
    "padded_fresh": [("fresh", 0, 5)],
    # a first chunk of a longer prompt run as a chunk at start 0
    "chunk_at_zero": [("chunk", 0, 8)],
    # two chunks of four: the second takes up state and tail
    "chunks_of_four": [("chunk", 0, 4), ("chunk", 4, 4)],
}


@pytest.mark.parametrize("route", sorted(STATE_ROUTES))
def test_every_route_to_a_position_leaves_the_same_state(net32, route):
    """State, tail and ring after eight positions are the same whether
    they came in one whole bucket, in a padded bucket and ticks, in
    chunks that hand the state on, or in a chunk at position 0 of a slot
    that held another request: padding rows, the stale state of the last
    tenant and the order of chunk and tick leave no trace."""
    toks = _prompt(8)
    want_cache = net32.prefill_paged(toks[None], 8, 0, _pages(0),
                                     _cache(net32), fresh=True)[1]
    want = _state(want_cache, 0)
    # the slot held another request before
    cache = net32.prefill_paged(_prompt(7)[None, :4], 4, 0, _pages(0),
                                _cache(net32), start=0, last=False)[1]
    at = 0
    for kind, start, n in STATE_ROUTES[route]:
        w = 8 if kind == "fresh" else max(n, 4)
        row = np.zeros((1, w), np.int32)
        row[0, :n] = toks[start:start + n]
        _, cache = net32.prefill_paged(
            row, n, 0, _pages(0), cache, start=start,
            fresh=kind == "fresh", last=True)
        at = start + n
    live = np.array([1, 0], np.int32)
    other = _state(cache, 1)
    while at < 8:
        step = np.array([toks[at], 0], np.int32)
        _, cache = net32.decode_step_paged(step, live, cache)
        at += 1
    got = _state(cache, 0)
    for k in want:
        # ring entries 0..7 hold the eight positions; the others none
        np.testing.assert_allclose(got[k][..., :8, :] if "ring" in k
                                   else got[k],
                                   want[k][..., :8, :] if "ring" in k
                                   else want[k], atol=2e-5, err_msg=k)
    assert int(cache["len"][0]) == 8 and int(cache["len"][1]) == 0
    # the inactive row of the ticks kept its state, tail and ring
    for k, v in _state(cache, 1).items():
        np.testing.assert_array_equal(v, other[k], err_msg=k)


def test_an_inactive_tick_row_leaves_no_trace(net32):
    toks = _prompt(6)
    cache = _cache(net32)
    for slot in (0, 1):
        _, cache = net32.prefill_paged(
            np.pad(toks, (0, 2))[None], 6, slot, _pages(slot), cache,
            fresh=True)
    before = _state(cache, 1)
    pool = np.asarray(cache["k"][_pages(1)], np.float32)
    _, cache = net32.decode_step_paged(
        np.array([5, 9], np.int32), np.array([1, 0], np.int32), cache)
    for k, v in _state(cache, 1).items():
        np.testing.assert_array_equal(v, before[k], err_msg=k)
    np.testing.assert_array_equal(
        np.asarray(cache["k"][_pages(1)], np.float32), pool)
    assert [int(x) for x in cache["len"]] == [7, 6]


def test_a_reused_slot_reads_nothing_of_the_last_tenant(net):
    """Request B in the slot request A just left reads the same logits
    as B alone in a fresh engine: the states are cleared at position 0,
    the rings masked by position, the pool's pages re-bound."""
    rng = np.random.default_rng(5)
    a = rng.integers(0, 512, 40).astype(np.int32)
    b = rng.integers(0, 512, 13).astype(np.int32)
    # one request at a time: each takes the first free slot, slot 0
    ((_, alone),), _ = serve(net, [(b, 6)])
    (_, (_, after)), _ = serve(net, [(a, 12), (b, 6)])
    np.testing.assert_array_equal(after, alone)


# -- the chunk that is not a prompt's last ---------------------------------------
def test_chunks_but_the_last_run_the_self_decoder_only(net32):
    """The engine says which chunk is last; the others dispatch the
    program that holds no layer above the self-decoder and no head, and
    the first token is the reference's all the same."""
    names = []
    progs = net32._ensure_programs()
    saved = dict(progs)
    for key, fn in saved.items():
        def spy(*args, _fn=fn, _key=key):
            names.append(_key)
            return _fn(*args)
        progs[key] = spy
    try:
        prompt = _prompt(21)
        ((tokens, rows),), calls = serve(net32, [(prompt, 3)])
    finally:
        progs.update(saved)
    assert calls == [(False, False), (False, False), (False, True)]
    assert names[:3] == ["chunk", "chunk", "chunk_last"]
    assert set(names[3:]) == {"decode"}
    ref = reference_rows(prompt, tokens)
    assert tokens[0] == int(ref[0].argmax())
    assert np.abs(rows - ref).max() <= TOL32
    # the program of a chunk that is not last reads no cross-decoder leaf
    i32 = lambda *shape: jnp.zeros(shape, jnp.int32)  # noqa: E731
    args = (net32._datas(), i32(1, CHUNK), i32(), i32(), i32(),
            i32(S_MAX // PAGE), _cache(net32))
    used = {}
    for key in ("chunk", "chunk_last"):
        jaxpr = jax.make_jaxpr(saved[key].__wrapped__)(*args)
        flat = jax.tree_util.tree_leaves_with_path(args[0])
        live = {v for eq in jaxpr.jaxpr.eqns for v in eq.invars
                if not hasattr(v, "val")}          # a Literal is hashable, no invar
        used[key] = {jax.tree_util.keystr(path)
                     for (path, _), var in zip(flat, jaxpr.jaxpr.invars)
                     if var in live}
    assert not any("cross_" in k or "final_" in k for k in used["chunk"])
    assert any("cross_" in k for k in used["chunk_last"])
    assert any("final_" in k for k in used["chunk_last"])


def test_counters_tell_the_rows_that_skipped_the_cross_decoder(net32):
    pre = "model.phi4flash."
    names = ("self_rows", "cross_rows", "ssm_token_layers.prefill",
             "ssm_token_layers.decode", "keys_attended", "window_keys")
    c0 = {n: telemetry.counter_value(pre + n) for n in names}
    serve(net32, [(_prompt(21), 3)])
    got = {n: telemetry.counter_value(pre + n) - c0[n] for n in names}
    # 21 rows through the self-decoder, one through the cross-decoder;
    # three Mamba layers; the first token comes from the prefill, two
    # ticks follow at contexts 22 and 23
    assert got["self_rows"] == 21 and got["cross_rows"] == 1
    assert got["ssm_token_layers.prefill"] == 3 * 21
    assert got["ssm_token_layers.decode"] == 3 * 2
    # the full layer and one cross layer read the pool
    assert got["keys_attended"] == 2 * (21 + 22 + 23)
    assert got["window_keys"] == 2 * (sum(min(t, 8) for t in range(1, 22))
                                      + 8 + 8)


# -- the comparison that decides ``correct`` sees each mechanism ---------------
@pytest.fixture(scope="module")
def served(net32):
    """One request of the float32 model past window, chunk and ring."""
    prompt = _prompt(41)
    ((tokens, _),), _ = serve(net32, [(prompt, 10)])
    gaps, hits = R.served_gaps(MODEL, W.make(MODEL, SEED), prompt, tokens,
                               10)
    assert hits.all() and gaps.max() == 0.0
    return prompt, tokens


@pytest.mark.parametrize("fault", R.FAULTS)
def test_a_planted_fault_of_a_mechanism_shows_in_the_served_gap(
        served, fault):
    """The greedy tokens of a forward whose state, ring, memory or
    ``lam`` is broken lie under the reference's best by several times
    what the bfloat16 program's own do (``GAP16``): the comparison that
    decides ``correct`` sees each mechanism."""
    prompt, tokens = served
    gaps, hits = R.served_gaps(MODEL, W.make(MODEL, SEED), prompt, tokens,
                               10, control=fault)
    assert gaps.max() > 4 * GAP16 and not hits.all()


@pytest.mark.parametrize("lowp", ["int8", "fp8"])
def test_the_control_precisions_move_every_logit(served, lowp):
    prompt, tokens = served
    ref = reference_rows(prompt, tokens)
    low = reference_rows(prompt, tokens, lowp)
    assert np.isfinite(low).all()
    assert np.abs(low - ref).max(-1).min() > 20 * TOL32


# -- the engine's side of the contract --------------------------------------------
def test_no_trace_after_warmup(net):
    with engine(net) as eng:
        eng.warmup()
        before = telemetry.counter_value("model.phi4flash.trace")
        for n in (3, 8, 9, 30):
            eng.submit(np.arange(n, dtype=np.int32) + 1,
                       max_new_tokens=4).result(timeout=300)
        assert telemetry.counter_value("model.phi4flash.trace") == before


def test_a_model_that_states_no_prefill_last_is_called_as_before():
    """``last=`` reaches only a model whose ``generation_support`` asks
    for it: the other families' calls are the ones they always were."""
    import inspect

    from mxnet_tpu.gluon.model_zoo.dots3 import Dots3Model
    from mxnet_tpu.gluon.model_zoo.gpt import GPTModel
    for cls in (Dots3Model, GPTModel):
        assert "last" not in inspect.signature(
            cls.prefill_paged).parameters


REFUSED = [
    ("paged", dict(paged=False)), ("prefix_cache", dict(prefix_cache=True)),
    ("quantize", dict(quantize="int8_weights")),
    ("kv_dtype", dict(kv_dtype="int8")),
    ("cache_dtype", dict(cache_dtype="float32")),
    ("decode_ticks", dict(decode_ticks=2)),
    ("lora_rank", dict(lora_rank=4)),
    ("mesh_layout", dict(mesh_layout="tp")),
    ("compute_dtype", dict(compute_dtype="float32")),
    ("compute_dtype", dict(compute_dtype=None)),
    ("prefill_chunk", dict(prefill_chunk=16)),
    ("speculative", dict(speculative=True)),
    ("draft_model", dict(draft_model=object())),
]


@pytest.mark.parametrize("option,kwargs", REFUSED,
                         ids=[f"{o}-{i}" for i, (o, _) in
                              enumerate(REFUSED)])
def test_engine_refuses_what_the_family_does_not_support(net, option,
                                                         kwargs):
    with pytest.raises(ValueError, match=rf"^{option}="):
        engine(net, **kwargs)


def test_the_constructor_refuses_a_depth_that_has_no_two_halves():
    keys = {k: MODEL[k] for k in P._KEYS}
    with pytest.raises(ValueError, match="num_hidden_layers"):
        Phi4FlashModel(**dict(keys, num_hidden_layers=6))
    with pytest.raises(ValueError, match="pair up"):
        Phi4FlashModel(**dict(keys, num_key_value_heads=1))


def test_bytes_held_are_two_a_parameter(net):
    params = net.collect_params()
    n = sum(int(np.prod(p.shape)) for p in params.values())
    assert n == net.parameter_count() == W.parameter_count(W.sizes(MODEL))
    held = sum(p.data()._data.nbytes for p in params.values())
    # two bytes a parameter, and two more for the float32 leaves
    # (A_log, D, b_dt and the lam vectors: 0.03 % of the parameters at
    # the published widths; more of this toy)
    wide = {k: p for k, p in params.items() if p.data()._data.dtype.itemsize
            == 4}
    assert {k.split("_", 2)[2] for k in wide} == set(W.FLOAT32_IN_PROGRAM)
    assert held == 2 * n + 2 * sum(int(np.prod(p.shape))
                                   for p in wide.values())
    assert all(p.grad_req == "null" and p.data()._grad is None
               for p in params.values())
    live = lambda: sum(a.nbytes for a in jax.live_arrays())  # noqa: E731
    before = live()
    with engine(net) as eng:
        cache = sum(a.nbytes for a in jax.tree_util.tree_leaves(eng._cache))
        # the engine adds its cache and nothing else: no cast shadow
        assert live() - before - cache < 0.02 * held


# -- ops/ssm.py -------------------------------------------------------------------
def _scan_inputs(t, ch, n, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 7)
    return (jax.random.normal(k[0], (t, ch)),
            jax.nn.softplus(jax.random.normal(k[1], (t, ch)) - 2.0),
            -jnp.exp(0.5 * jax.random.normal(k[2], (n, ch))),
            jax.random.normal(k[3], (t, n)), jax.random.normal(k[4], (t, n)),
            jax.random.normal(k[5], (ch,)), jax.random.normal(k[6], (n, ch)))


@pytest.mark.parametrize("t,ch,n,n_valid", [
    (16, 256, 4, 16), (16, 256, 4, 11), (24, 1024, 16, 1),
    (40, 2048, 16, 33)], ids=["whole", "padded", "one-row", "two-blocks"])
def test_scan_kernel_matches_the_jnp_path_in_interpret_mode(t, ch, n,
                                                            n_valid):
    """The Pallas kernel (channels blocked, time walked inside, the
    state carried in and out) against the ``lax.scan`` it stands for,
    with a state taken up (``h0``) and a chunk padded past ``n_valid``."""
    x, dt, a, b, c, d, h0 = _scan_inputs(t, ch, n)
    want_m, want_h = ssm.selective_scan(x, dt, a, b, c, d, h0,
                                        jnp.int32(n_valid))
    live = (jnp.arange(t) < n_valid)[:, None]
    got_m, got_h = ssm.selective_scan_pallas(
        x, jnp.where(live, dt, 0.0), a, b, c, d, h0, interpret=True)
    np.testing.assert_allclose(got_m[:n_valid], want_m[:n_valid], atol=2e-5)
    np.testing.assert_allclose(got_h, want_h, atol=2e-5)
    # the state is the one a scan of the valid rows alone leaves
    _, alone = ssm.selective_scan(x[:n_valid], dt[:n_valid], a, b[:n_valid],
                                  c[:n_valid], d, h0, jnp.int32(n_valid))
    np.testing.assert_allclose(got_h, alone, atol=2e-5)


def test_a_step_is_a_scan_of_one_row_and_spares_inactive_rows():
    x, dt, a, b, c, d, h0 = _scan_inputs(2, 256, 4)
    h = jnp.stack([h0, 2.0 * h0])
    m, new = ssm.selective_step(x, dt, a, b, c, d, h,
                                jnp.asarray([True, False]))
    want_m, want_h = ssm.selective_scan(x[:1], dt[:1], a, b[:1], c[:1], d,
                                        h0, jnp.int32(1))
    np.testing.assert_allclose(m[0], want_m[0], atol=2e-5)
    np.testing.assert_allclose(new[0], want_h, atol=2e-5)
    np.testing.assert_array_equal(new[1], h[1])


def test_the_convolution_hands_on_the_tail_before_n_valid():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(8, 128)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(4, 128)), jnp.float32)
    bias = jnp.asarray(rng.normal(size=(128,)), jnp.float32)
    tail = jnp.asarray(rng.normal(size=(3, 128)), jnp.float32)
    y, new = ssm.causal_conv_chunk(x, w, bias, tail, jnp.int32(5))
    xp = np.concatenate([tail, x])
    want = bias + sum(w[k] * xp[k:k + 8] for k in range(4))
    np.testing.assert_allclose(y, want, atol=1e-5)
    np.testing.assert_array_equal(new, x[2:5])
    # fewer valid rows than taps: the old tail's last rows stay
    _, new = ssm.causal_conv_chunk(x, w, bias, tail, jnp.int32(1))
    np.testing.assert_array_equal(new, xp[1:4])
    y1, t1 = ssm.causal_conv_step(x[5:7], w, bias, jnp.stack([new, new]),
                                  jnp.asarray([True, False]))
    np.testing.assert_array_equal(t1[1], new)
    np.testing.assert_array_equal(t1[0], np.concatenate([new[1:], x[5:6]]))


# -- the family's costs -----------------------------------------------------------
def test_costs_count_one_cross_decoder_row_a_prompt():
    s = W.sizes(MODEL)
    assert C.cross_decoder_rows(30) == 1
    # a prompt one token longer costs one more self-decoder row (its
    # window's keys, its K and V in the pool) and one more key for the
    # ONE row of the full layer and the cross layer
    kv = s["Hkv"] * s["dh"]
    self_row = sum(2 * (C._mixer_params(s, k) + C._mlp(s))
                   for k in (W.SSM, W.SSM, W.SSM, W.SWA, W.SWA)) \
        + 3 * C._scan_flops(s) + 2 * C._per_key(s) * s["window"] \
        + 2 * s["D"] * 2 * kv
    d = C.prompt_forward_flops(s, 31) - C.prompt_forward_flops(s, 30)
    assert d == pytest.approx(self_row + 2 * C._per_key(s))
    # a decoded token passes every layer
    whole = C.token_forward_flops(s, 31, True)
    assert whole > self_row + 2 * s["V"] * s["D"]
    assert C.token_forward_flops(s, 41, True) - C.token_forward_flops(
        s, 40, True) == 2 * C._per_key(s)
    with pytest.raises(SystemExit):
        C.train_step_flops(s, 1, 1)


def test_the_scan_roofline_counts_a_floor_of_whole_chunks():
    s = W.sizes(MODEL)
    facts = {"sizes": s, "counters": {
        "serving.generate.prefill_chunks": 7, "serving.generate.prefills": 3}}
    flops, nbytes = C.ssm_scan_call(facts)
    tokens = (7 - 3) * CHUNK * 3
    assert flops == tokens * 4 * s["C"] * s["N"]
    assert nbytes > 4 * tokens * 3 * s["C"]
    facts["counters"]["serving.generate.prefills"] = 9
    assert C.ssm_scan_call(facts) == (0, 0)


def test_a_greedy_tick_is_picked_on_the_device(net32):
    """An all-greedy tick's argmax runs where the logits lie (the
    published model's 32 x 200,064 float32 logits are 25.6 MB a tick):
    the ints that come back are the host argmax's of the same rows, and
    nothing is traced after ``warmup()``."""
    seen = []
    with engine(net32) as eng:
        eng.warmup()
        samplers = eng._ensure_samplers()
        pick = samplers["greedy"]

        def spy(logits):
            seen.append((np.asarray(logits).argmax(-1),
                         np.asarray(pick(logits))))
            return seen[-1][1]
        samplers["greedy"] = spy
        before = telemetry.counter_value("ops.sampling.trace")
        eng.submit(_prompt(21), max_new_tokens=6).result(timeout=300)
        assert telemetry.counter_value("ops.sampling.trace") == before
    assert len(seen) == 5
    for host, device in seen:
        assert device.dtype == np.int32 and (device == host).all()
