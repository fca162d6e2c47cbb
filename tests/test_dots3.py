"""The ``dots3_note`` decoder family at a toy size that keeps every ratio
of the published model: two full-attention and three window layers, a
window (9) smaller than the longest prompt, an ``index_topk`` (12) smaller
than the longest context, 16 routed experts of which 4 are held, top 4.

The system (``Dots3Model`` through ``GenerationEngine``: chunked prefill,
then decode through the paged pools and the rings) is compared with the
plain float32 reference of ``chipbench/families/dots3/`` on the
benchmark's seeded weights: logits, not tokens. A bfloat16 program and a
float32 reference may order two near-equal router or index scores
differently, and at this size one such choice moves a logit by more than
bfloat16 does, so a position is compared only where the *reference's own*
margins of those choices are wide (``MARGIN``); the share left out is
bounded. CPU, under a minute together.
"""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu import telemetry  # noqa: E402
from mxnet_tpu.gluon.model_zoo.dots3 import Dots3Model  # noqa: E402
from mxnet_tpu.ops import moe  # noqa: E402
from mxnet_tpu.serving import GenerationEngine  # noqa: E402

from chipbench.families.dots3 import costs as C  # noqa: E402
from chipbench.families.dots3 import program as P  # noqa: E402
from chipbench.families.dots3 import reference as R  # noqa: E402
from chipbench.families.dots3 import weights as W  # noqa: E402

MODEL = dict(
    vocab_size=512, hidden_size=64, num_hidden_layers=5,
    layer_types=["full_attention", "full_attention", "sliding_attention",
                 "sliding_attention", "sliding_attention"],
    num_attention_heads=4, q_lora_rank=32, kv_lora_rank=16,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    index_n_heads=4, index_head_dim=16, index_topk=12,
    sliding_window_size=9, swa_num_attention_heads=2, swa_q_lora_rank=32,
    swa_kv_lora_rank=32, swa_qk_nope_head_dim=24, swa_qk_rope_head_dim=8,
    swa_v_head_dim=16, intermediate_size=160, moe_intermediate_size=32,
    n_routed_experts=4, router_experts=16, expert_rank=1,
    num_experts_per_tok=4, n_shared_experts=1, first_k_dense_replace=1,
    rope_theta=8e7, swa_rope_theta=5e4, rms_norm_eps=1e-5,
    initializer_range=0.05)
SEED = 27
CHUNK, PAGE, S_MAX = 8, 4, 64
#: float32 leaves against the float32 reference: rounding only (the CPU's
#: products against ``Precision.HIGHEST``, the absorbed against the plain
#: form), no choice is ever ordered differently
TOL32 = 2e-3
#: bfloat16 leaves, where the reference's own narrowest index and router
#: margins at a position are at least ``MARGIN`` (bfloat16 moves a sigmoid
#: score by ~4e-4 here): bfloat16's rounding over five layers reads
#: 0.025-0.08 there, and up to 0.28 where a choice is narrow
MARGIN, TOL16 = 0.002, 0.12


def build(dtype="bfloat16", model=MODEL, seed=SEED, chunk=CHUNK):
    """The toy model as ``families/dots3/program.build_model`` builds the
    real one, with a ring small enough to wrap (window 9 + chunk 8 - 1).
    ``dtype`` float32 installs the seeded weights unrounded."""
    s = W.sizes(model)
    net = Dots3Model(
        n_routed_experts=s["E_all"],
        experts_held=range(s["E_lo"], s["E_lo"] + s["E_held"]),
        max_length=S_MAX, prefill_chunk=chunk, dtype=dtype,
        **{k: model[k] for k in P._KEYS})
    P.install(net, W.make(model, seed), for_program=dtype == "bfloat16")
    return net


def engine(net, slots=2, **more):
    args = dict(max_slots=slots, max_length=S_MAX, paged=True,
                page_size=PAGE, prefill_chunk=CHUNK, prefix_cache=False,
                compute_dtype=net.generation_support["compute_dtype"][0],
                max_new_tokens=16)
    args.update(more)
    return GenerationEngine(net, **args)


class Spy:
    """Records the logits the engine's model calls return: the last
    prefill chunk's row for a slot, then each decode tick's."""

    def __init__(self, net):
        self.net, self.rows = net, {}
        self._prefill, self._decode = net.prefill_paged, \
            net.decode_step_paged
        net.prefill_paged, net.decode_step_paged = self.prefill, self.decode

    def prefill(self, tokens, n_valid, slot, pages, cache, **kw):
        lg, cache = self._prefill(tokens, n_valid, slot, pages, cache, **kw)
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
    return out


def reference_rows(prompt, tokens):
    seq = np.concatenate([prompt, tokens]).astype(np.int32)
    row = np.zeros((64,), np.int32)
    row[:len(seq)] = seq
    logits, (mi, mr) = R.logits_rows(
        MODEL, W.make(MODEL, SEED), row, len(prompt) - 1, len(tokens),
        margins=True)
    return np.asarray(logits), np.minimum(np.asarray(mi), np.asarray(mr))


#: (prompt, new): fresh in one bucket, nothing wraps; chunks that pass the
#: window (9) and index_topk (12) once; a context that passes the ring
#: (16) and index_topk twice over
SHAPES = [(5, 6), (21, 8), (38, 14)]


def _prompt(n):
    return np.random.default_rng(n).integers(
        0, MODEL["vocab_size"], n).astype(np.int32)


@pytest.fixture(scope="module")
def served32(net32):
    return serve(net32, [(_prompt(n), k) for n, k in SHAPES])


@pytest.mark.parametrize("case", range(len(SHAPES)))
def test_engine_logits_follow_the_reference(served32, case):
    """Prefill in chunks, then decode through the pools and the rings,
    against the reference's one full forward pass: every logit of every
    served position."""
    n_prompt, n_new = SHAPES[case]
    tokens, rows = served32[case]
    assert len(tokens) == n_new
    ref, _ = reference_rows(_prompt(n_prompt), tokens)
    assert np.abs(rows - ref).max() <= TOL32
    assert tokens == [int(r.argmax()) for r in ref]


def test_context_passes_ring_and_index_topk_twice(net32, served32):
    n_prompt, n_new = SHAPES[-1]
    assert net32.ring_size == 16 and MODEL["index_topk"] == 12
    assert n_prompt + n_new > 2 * net32.ring_size
    assert n_prompt + n_new > 2 * MODEL["index_topk"]
    # the last decoded position reads a ring written three times over
    tokens, rows = served32[-1]
    ref, _ = reference_rows(_prompt(n_prompt), tokens)
    assert np.abs(rows[-1] - ref[-1]).max() <= TOL32


def test_bfloat16_follows_the_reference_where_its_choices_are_wide(net):
    """The model as it is served (bfloat16 leaves, pools and rings). A
    position is compared where the reference's own margins are wide: at
    this size one flipped expert or key moves a logit by more than
    bfloat16 does."""
    prompt = _prompt(38)
    (tokens, rows), = serve(net, [(prompt, 14)])
    ref, margin = reference_rows(prompt, tokens)
    gap = np.abs(rows - ref).max(-1)
    wide = margin >= MARGIN
    assert wide.sum() >= 3, margin
    assert gap[wide].max() <= TOL16, (gap, margin)
    assert np.median(gap) <= TOL16 / 2


@pytest.fixture(scope="module")
def served(net32):
    """One request of the float32 model past ring, window and
    ``index_topk``, with its gap under the reference."""
    prompt = _prompt(38)
    (tokens, _), = serve(net32, [(prompt, 14)])
    gaps, hits = R.served_gaps(MODEL, W.make(MODEL, SEED), prompt, tokens,
                               14)
    assert hits.all() and gaps.max() == 0.0
    return prompt, tokens


@pytest.mark.parametrize("fault", R.FAULTS)
def test_a_planted_fault_of_a_mechanism_shows_in_the_served_gap(
        served, fault):
    """The greedy tokens of a forward whose selection, window or expert
    is broken lie under the reference's best: the comparison that decides
    ``correct`` sees each mechanism."""
    prompt, tokens = served
    gaps, hits = R.served_gaps(MODEL, W.make(MODEL, SEED), prompt, tokens,
                               14, control=fault)
    assert gaps.max() > TOL16 and not hits.all()


def test_a_reused_slot_reads_nothing_of_the_last_tenant(net):
    """Request B in the slot request A just left reads the same logits
    as B alone in a fresh engine (the ring is never cleared, only masked
    by position; the pools' pages are re-bound)."""
    rng = np.random.default_rng(5)
    a = rng.integers(0, 512, 40).astype(np.int32)
    b = rng.integers(0, 512, 13).astype(np.int32)
    # one request at a time: each takes the first free slot, slot 0
    (_, alone), = serve(net, [(b, 6)])
    _, (_, after) = serve(net, [(a, 12), (b, 6)])
    np.testing.assert_array_equal(after, alone)


def test_no_trace_after_warmup(net):
    with engine(net) as eng:
        eng.warmup()
        before = telemetry.counter_value("model.dots3.trace")
        k0 = telemetry.counter_value("model.dots3.keys_selected")
        c0 = telemetry.counter_value("model.dots3.keys_in_context")
        h0 = telemetry.counter_value("model.dots3.experts_hit.decode")
        for n in (5, 9, 30):
            eng.submit(np.arange(n, dtype=np.int32) + 1,
                       max_new_tokens=4).result(timeout=300)
        assert telemetry.counter_value("model.dots3.trace") == before
        sel = telemetry.counter_value("model.dots3.keys_selected") - k0
        ctx = telemetry.counter_value("model.dots3.keys_in_context") - c0
        hit = telemetry.counter_value("model.dots3.experts_hit.decode") - h0
    # nine ticks, four routed layers, 4 experts held; the last calls'
    # counts may still be on the device
    assert 0 < hit <= 9 * 4 * 4
    # three decode ticks a request, two full layers: contexts 6-8, 10-12
    # (all kept: at most index_topk 12) and 31-33 (12 kept of each)
    assert ctx == 2 * (6 + 7 + 8 + 10 + 11 + 12 + 31 + 32 + 33)
    assert sel == 2 * (6 + 7 + 8 + 10 + 11 + 12 + 12 + 12 + 12)


def test_warmup_covers_the_tail_chunk_with_no_prefix_index(net):
    """Without a prefix index chunks start at multiples of the chunk
    width, so ``warmup()`` compiles two chunk widths, the whole one and
    the one that reaches the cache's end, and no traffic traces."""
    with engine(net, max_length=S_MAX - PAGE) as eng:
        assert eng._chunk_widths() == [CHUNK - PAGE, CHUNK]
        eng.warmup()
        before = telemetry.counter_value("model.dots3.trace")
        for n in (3, CHUNK, 30, S_MAX - PAGE - 1):
            eng.submit(np.arange(n, dtype=np.int32) + 1,
                       max_new_tokens=1).result(timeout=300)
        assert telemetry.counter_value("model.dots3.trace") == before


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


def test_bytes_held_are_two_a_parameter(net):
    params = net.collect_params()
    n = sum(int(np.prod(p.shape)) for p in params.values())
    assert n == net.parameter_count() == W.parameter_count(W.sizes(MODEL))
    held = sum(p.data()._data.nbytes for p in params.values())
    # two bytes a parameter, and two more for the float32 leaves: the
    # router and the full-attention layers' indexer branch (0.5 % of the
    # parameters at the published widths; more of this toy)
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


# -- the expert layer ---------------------------------------------------------
def _routed_inputs(seed, t=24):
    s = W.sizes(dict(MODEL, n_routed_experts=16, expert_rank=0))
    lw = W.Weights(dict(MODEL, n_routed_experts=16, expert_rank=0),
                   seed).layer(1)
    z = jax.random.normal(jax.random.PRNGKey(seed), (t, s["D"]),
                          jnp.float32)
    return s, lw, z


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_shares_add_up_to_the_uncut_layer(seed):
    """Four ranks of 4 experts each: the parts of the routed result that
    the ranks compute (the program's ``expert_layer``, told which experts
    it holds) and the shared expert counted once add up to what the
    uncut reference gives for the whole layer."""
    s, lw, z = _routed_inputs(seed)
    whole = R.routed_share(s, lw, z, lo=0) + R._swiglu(
        z, lw["s_gate"], lw["s_up"], lw["s_down"], None)
    # the reference's own shares, exactly
    s4 = dict(s, E_held=4)
    parts = sum(R.routed_share(
        s4, {k: (v[lo:lo + 4] if k.startswith("e_") else v)
             for k, v in lw.items()}, z, lo=lo) for lo in (0, 4, 8, 12))
    shared = R._swiglu(z, lw["s_gate"], lw["s_up"], lw["s_down"], None)
    np.testing.assert_allclose(parts + shared, whole, atol=2e-5)
    # the program's shares, in bfloat16
    bf = jnp.bfloat16
    zb = z.astype(bf)
    ids, gates = moe.route_sigmoid_topk(zb, lw["router"],
                                        lw["router_bias"], s["K"])
    shares = [moe.expert_layer(
        zb, lw["e_gate"][lo:lo + 4].astype(bf),
        lw["e_up"][lo:lo + 4].astype(bf),
        lw["e_down"][lo:lo + 4].astype(bf), ids, gates, lo, 8)
        for lo in (0, 4, 8, 12)]
    got = sum(out for out, _ in shares)
    # over the shares, every expert some token chose streams once
    assert sum(int(n) for _, n in shares) == len(np.unique(np.asarray(ids)))
    ref_ids, _, margin = R.route(s, lw, z)
    same = np.asarray(jnp.sort(ids, -1) == jnp.sort(ref_ids, -1)).all(-1)
    assert same[np.asarray(margin) > 1e-3].all()
    np.testing.assert_allclose(
        np.asarray(got + shared)[same], np.asarray(whole)[same],
        atol=0.03)


@pytest.mark.parametrize("tm", [8, 32])
def test_dispatch_is_dropless_and_tile_aligned(tm):
    rng = np.random.default_rng(tm)
    t, k, e_all, lo, e = 50, 4, 16, 4, 4
    ids = jnp.asarray(np.stack([rng.permutation(e_all)[:k]
                                for _ in range(t)]).astype(np.int32))
    row_token, pick_row, tile_expert, n_active, n_hit = moe.dispatch(
        ids, lo, e, tm)
    m = row_token.shape[0]
    assert m == moe.rows_for(t * k, e, tm) and m % tm == 0
    held = np.asarray((ids >= lo) & (ids < lo + e))
    rows = np.asarray(pick_row)
    # every held pick has a row of its own, no other pick has one
    assert (rows[held] < int(n_active) * tm).all() and (rows[~held] == m).all()
    assert len(set(rows[held])) == held.sum()
    assert int(n_hit) == len(set(np.asarray(ids)[held]))
    # and the row lies in a tile of the pick's expert, reading its token
    np.testing.assert_array_equal(
        np.asarray(tile_expert)[rows[held] // tm],
        np.asarray(ids)[held] - lo)
    np.testing.assert_array_equal(
        np.asarray(row_token)[rows[held]],
        np.broadcast_to(np.arange(t)[:, None], (t, k))[held])


def test_grouped_kernel_matches_the_jnp_path_in_interpret_mode():
    rng = np.random.default_rng(0)
    t, k, lo, e, d, f, tm = 40, 4, 4, 4, 256, 128, 8
    ids = jnp.asarray(np.stack([rng.permutation(16)[:k]
                                for _ in range(t)]).astype(np.int32))
    row_token, _, tile_expert, n_active, _ = moe.dispatch(ids, lo, e, tm)
    x = jnp.asarray(rng.normal(size=(t, d)), jnp.bfloat16)[row_token]
    w = jnp.asarray(rng.normal(size=(e, d, f)) * 0.05, jnp.bfloat16)
    want = moe.grouped_matmul(x, w, tile_expert, n_active, tm)
    with pltpu.force_tpu_interpret_mode():
        got = moe.grouped_matmul_pallas(x, w, tile_expert, n_active, tm)
    n = int(n_active) * tm
    np.testing.assert_allclose(np.asarray(got[:n], np.float32),
                               np.asarray(want[:n], np.float32), atol=0.02)


# -- the model as a block ------------------------------------------------------
def test_forward_equals_prefill_then_decode(net32, served32):
    tokens, rows = served32[1]
    seq = np.concatenate([_prompt(SHAPES[1][0]), tokens]).astype(np.int32)
    whole = np.asarray(net32(mx.np.array(seq[None]))._data)[0]
    n = SHAPES[1][0]
    assert np.abs(whole[n - 1:n - 1 + len(tokens)] - rows).max() <= TOL32


def test_the_constructor_refuses_a_share_that_is_no_range():
    keys = {k: MODEL[k] for k in P._KEYS}
    with pytest.raises(ValueError, match="experts_held"):
        Dots3Model(n_routed_experts=16, experts_held=range(12, 20), **keys)
    with pytest.raises(ValueError, match="layer_types"):
        Dots3Model(n_routed_experts=16, **dict(
            keys, layer_types=["sliding_attention"]))


# -- the family's costs ---------------------------------------------------------
def test_costs_count_selected_keys_and_held_picks():
    s = W.sizes(MODEL)
    n = 30
    by_token = sum(C.token_forward_flops(s, t + 1, False) for t in range(n))
    assert C.prompt_forward_flops(s, n) == pytest.approx(
        by_token + 2 * s["V"] * s["D"])
    # past index_topk and the window a longer context costs the indexer's
    # scores only: 2 H_I d_I a key and full layer
    d = C.token_forward_flops(s, 41, True) - C.token_forward_flops(
        s, 40, True)
    assert d == 2 * (2 * s["HI"] * s["DI"])
    assert C.held_picks_per_token(s) == 4 * 4 / 16
    with pytest.raises(SystemExit):
        C.train_step_flops(s, 1, 1)
