"""The ``xing4_0`` decoder family at a toy size that keeps every ratio of
the published model: two dense and three routed layers, four residual
streams under hyper-connections with 20 Sinkhorn rounds, latent attention
with YaRN (whose scaled frequencies matter inside 64 positions here), 16
routed experts of which 4 a token, all held, gates scaled by 2.

The system (``Xing4Model`` through ``GenerationEngine``: chunked prefill,
then decode through the paged latent pools) is compared with the plain
float32 reference of ``chipbench/families/xing4/`` on the benchmark's
seeded weights: logits, not tokens. CPU, about a minute together.
"""
import math
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
from mxnet_tpu.ops import hyper_connection as hc  # noqa: E402
from mxnet_tpu.ops import latent_attention as la  # noqa: E402
from mxnet_tpu.ops import moe  # noqa: E402
from mxnet_tpu.serving import GenerationEngine  # noqa: E402

from chipbench import harness  # noqa: E402
from chipbench.families.xing4 import costs as C  # noqa: E402
from chipbench.families.xing4 import program as P  # noqa: E402
from chipbench.families.xing4 import reference as R  # noqa: E402
from chipbench.families.xing4 import weights as W  # noqa: E402

CHUNK, PAGE, S_MAX = 8, 4, 64
MODEL = dict(
    vocab_size=512, hidden_size=64, num_hidden_layers=5,
    num_nextn_predict_layers=0, num_attention_heads=4, q_lora_rank=32,
    kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    intermediate_size=160, moe_intermediate_size=32, n_routed_experts=16,
    num_experts_per_tok=4, n_shared_experts=1, first_k_dense_replace=2,
    routed_scaling_factor=2, rope_theta=10000, rms_norm_eps=1e-6,
    rope_scaling={"type": "yarn", "factor": 8, "beta_fast": 4,
                  "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1,
                  "original_max_position_embeddings": 16},
    hc_mult=4, hc_sinkhorn_iters=20, hc_eps=1e-6, mhc_h_res_clamp_min=-30,
    mhc_h_res_clamp_max=30, initializer_range=0.1)
SEED = 33
#: float32 leaves against the float32 reference: rounding only (the order
#: of a sum, the absorbed against the plain form)
TOL32 = 5e-5
#: bfloat16 leaves, pools and streams, where the reference's own router
#: margin at a position is at least ``MARGIN``: five layers of bfloat16
#: move a logit by 0.01-0.05 there, and by more where a pick is narrow
MARGIN, TOL16 = 0.002, 0.12
#: a planted fault has to move the served gap, at the positions the
#: reference judges (its own picks decided: ``R.DECIDED``), past this;
#: the float32 program reads exactly 0 there and the bfloat16 one 0.01
FAULT_GAP = 0.03


def build(dtype="bfloat16", model=MODEL):
    """The toy model as ``families/xing4/program.build_model`` builds the
    real one; ``float32`` installs the seeded weights unrounded in dtype
    (the values are those bfloat16 holds either way)."""
    return P.build_model(model, SEED, dtype=dtype, max_length=S_MAX)


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


def reference_rows(prompt, tokens, control=None):
    seq = np.concatenate([prompt, tokens]).astype(np.int32)
    row = np.zeros((64,), np.int32)
    row[:len(seq)] = seq
    logits, margin = R.logits_rows(
        MODEL, W.make(MODEL, SEED), row, len(prompt) - 1, len(tokens),
        control, margins=True)
    return np.asarray(logits), np.asarray(margin)


#: (prompt, new): fresh in one bucket; chunks across a chunk boundary (8)
#: and page boundaries (4) with a ragged last chunk; a context that ends
#: past YaRN's original 16 positions four times over
SHAPES = [(5, 6), (21, 8), (38, 14)]


def _prompt(n):
    return np.random.default_rng(n).integers(
        0, MODEL["vocab_size"], n).astype(np.int32)


@pytest.fixture(scope="module")
def served32(net32):
    return serve(net32, [(_prompt(n), k) for n, k in SHAPES])


@pytest.mark.parametrize("case", range(len(SHAPES)))
def test_engine_logits_follow_the_reference(served32, case):
    """Prefill in chunks, then decode through the pools, against the
    reference's one full forward pass: every logit of every served
    position."""
    n_prompt, n_new = SHAPES[case]
    tokens, rows = served32[case]
    assert len(tokens) == n_new
    ref, _ = reference_rows(_prompt(n_prompt), tokens)
    assert np.abs(rows - ref).max() <= TOL32
    assert tokens == [int(r.argmax()) for r in ref]


def test_bfloat16_follows_the_reference_where_its_picks_are_wide(net):
    """The model as it is served (bfloat16 leaves, pools and streams). A
    position is compared where the reference's own router margin is
    wide: at this size one flipped expert moves a logit by more than
    bfloat16 does."""
    prompt = _prompt(38)
    (tokens, rows), = serve(net, [(prompt, 14)])
    ref, margin = reference_rows(prompt, tokens)
    gap = np.abs(rows - ref).max(-1)
    wide = margin >= MARGIN
    assert wide.sum() >= 3, margin
    assert gap[wide].max() <= TOL16, (gap, margin)
    assert np.median(gap) <= TOL16 / 2


def test_forward_equals_prefill_then_decode(net32, served32):
    tokens, rows = served32[1]
    seq = np.concatenate([_prompt(SHAPES[1][0]), tokens]).astype(np.int32)
    whole = np.asarray(net32(mx.np.array(seq[None]))._data)[0]
    n = SHAPES[1][0]
    assert np.abs(whole[n - 1:n - 1 + len(tokens)] - rows).max() <= TOL32


@pytest.fixture(scope="module")
def served(net32):
    """One request of the float32 model across chunk and page boundaries
    and past YaRN's original length, with its gap under the reference."""
    prompt = _prompt(38)
    (tokens, _), = serve(net32, [(prompt, 14)])
    gaps, hits = R.served_gaps(MODEL, W.make(MODEL, SEED), prompt, tokens,
                               14)
    assert hits.all() and gaps.max() == 0.0
    return prompt, tokens


@pytest.mark.parametrize("fault", R.FAULTS)
def test_a_planted_fault_of_a_mechanism_shows_in_the_served_gap(
        served, fault):
    """The greedy tokens of a forward with one mechanism broken lie under
    the reference's best at the judged positions, and every logit row
    moves past the float32 tolerance: the comparison that decides
    ``correct`` sees each mechanism."""
    prompt, tokens = served
    gaps, hits = R.served_gaps(MODEL, W.make(MODEL, SEED), prompt, tokens,
                               14, control=fault)
    assert gaps.max() > FAULT_GAP and not hits.all()
    # a gap is reported where the reference's own picks are decided
    _, margin = reference_rows(prompt, tokens)
    assert 3 <= (margin >= R.DECIDED).sum() < len(tokens)
    assert (gaps[margin < R.DECIDED] == 0).all()
    ref, _ = reference_rows(prompt, tokens)
    broken, _ = reference_rows(prompt, tokens, control=fault)
    assert np.abs(broken - ref).max() > 100 * TOL32


@pytest.mark.parametrize("lowp", ["int8", "fp8"])
def test_the_control_precisions_move_every_logit_row(served, lowp):
    prompt, tokens = served
    ref, _ = reference_rows(prompt, tokens)
    low, _ = reference_rows(prompt, tokens, control=lowp)
    assert np.abs(low - ref).max(-1).min() > 10 * TOL32


# -- the engine's contract --------------------------------------------------------
def _cache(net, slots=2):
    return net.init_paged_cache(slots, slots * S_MAX // PAGE + 1, PAGE,
                                S_MAX)


def _pages(slot):
    n = S_MAX // PAGE
    return np.arange(1 + slot * n, 1 + (slot + 1) * n, dtype=np.int32)


def test_an_inactive_tick_row_leaves_no_trace(net32):
    toks = _prompt(6)
    cache = _cache(net32)
    for slot in (0, 1):
        _, cache = net32.prefill_paged(
            np.pad(toks, (0, 2))[None], 6, slot, _pages(slot), cache,
            fresh=True)
    before = [np.asarray(pool[_pages(1)]) for pool in cache["lat"]]
    _, cache = net32.decode_step_paged(
        np.array([5, 9], np.int32), np.array([1, 0], np.int32), cache)
    for pool, was in zip(cache["lat"], before):
        np.testing.assert_array_equal(np.asarray(pool[_pages(1)]), was)
    assert [int(x) for x in cache["len"]] == [7, 6]


def test_a_reused_slot_reads_nothing_of_the_last_tenant(net):
    rng = np.random.default_rng(5)
    a = rng.integers(0, 512, 40).astype(np.int32)
    b = rng.integers(0, 512, 13).astype(np.int32)
    # one request at a time: each takes the first free slot, slot 0
    (_, alone), = serve(net, [(b, 6)])
    _, (_, after) = serve(net, [(a, 12), (b, 6)])
    np.testing.assert_array_equal(after, alone)


def test_no_trace_after_warmup(net):
    names = ["model.xing4." + n for n in (
        "trace", "keys_attended", "hc_sublayer_rows.prefill",
        "hc_sublayer_rows.decode", "experts_hit.decode")]
    with engine(net) as eng:
        eng.warmup()
        c0 = {n: telemetry.counter_value(n) for n in names}
        for n in (5, 9, 30):
            eng.submit(np.arange(n, dtype=np.int32) + 1,
                       max_new_tokens=4).result(timeout=300)
        got = {n.split("xing4.")[1]: telemetry.counter_value(n) - c0[n]
               for n in names}
    assert got["trace"] == 0
    # three decode ticks a request over five layers: contexts 6-8, 10-12,
    # 31-33, each counted with the token's own position
    assert got["keys_attended"] == 5 * (6 + 7 + 8 + 10 + 11 + 12
                                        + 31 + 32 + 33)
    # ten sublayers a row: every prompt token, every decoded token
    assert got["hc_sublayer_rows.prefill"] == 10 * (5 + 9 + 30)
    assert got["hc_sublayer_rows.decode"] == 10 * 9
    # nine ticks, three routed layers, both slots' rows of four picks (a
    # tick routes every row, live or not); the last calls' counts may
    # still be on the device
    assert 0 < got["experts_hit.decode"] <= 9 * 3 * 2 * 4


REFUSED = [
    ("paged", dict(paged=False)), ("prefix_cache", dict(prefix_cache=True)),
    ("quantize", dict(quantize="int8_weights")),
    ("kv_dtype", dict(kv_dtype="int8")),
    ("cache_dtype", dict(cache_dtype="float32")),
    ("decode_ticks", dict(decode_ticks=2)),
    ("lora_rank", dict(lora_rank=4)),
    ("mesh_layout", dict(mesh_layout="tp")),
    ("compute_dtype", dict(compute_dtype="float32")),
    ("speculative", dict(speculative=True)),
]


@pytest.mark.parametrize("option,kwargs", REFUSED,
                         ids=[o for o, _ in REFUSED])
def test_engine_refuses_what_the_family_does_not_support(net, option,
                                                         kwargs):
    with pytest.raises(ValueError, match=rf"^{option}="):
        engine(net, **kwargs)


def test_the_constructor_refuses_a_rotary_scaling_it_does_not_build():
    keys = {k: MODEL[k] for k in P._KEYS}
    from mxnet_tpu.gluon.model_zoo.xing4 import Xing4Model
    with pytest.raises(ValueError, match="yarn"):
        Xing4Model(**dict(keys, rope_scaling={"type": "linear"}))
    with pytest.raises(ValueError, match="mscale"):
        Xing4Model(**dict(keys, rope_scaling=dict(
            MODEL["rope_scaling"], mscale=0.7)))
    with pytest.raises(SystemExit, match="next-token"):
        W.sizes(dict(MODEL, num_nextn_predict_layers=1))


def test_bytes_held_are_two_a_parameter(net):
    params = net.collect_params()
    n = sum(int(np.prod(p.shape)) for p in params.values())
    assert n == net.parameter_count() == W.parameter_count(W.sizes(MODEL))
    held = sum(p.data()._data.nbytes for p in params.values())
    wide = {k: p for k, p in params.items()
            if p.data()._data.dtype.itemsize == 4}
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


# -- the hyper-connection ------------------------------------------------------------
def _hc_inputs(seed, t=24, dtype=jnp.float32, b_scale=1.0):
    s = W.sizes(MODEL)
    lw = W.make(MODEL, seed).layer(3)
    x = jax.random.normal(jax.random.PRNGKey(seed), (t, s["n"], s["D"]),
                          jnp.float32).astype(dtype)
    return s, lw["a_phi"], lw["a_alpha"], b_scale * lw["a_b"], x


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_three_maps_follow_the_reference(seed):
    s, phi, alpha, b, x = _hc_inputs(seed)
    got = hc.coefficients(x, phi, alpha, b, iters=s["iters"],
                          eps=s["hc_eps"], clamp=tuple(s["clamp"]))
    want = R.hc_maps(s, phi, alpha, b, x)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, atol=2e-6)
    # the maps follow the token: no two tokens share them
    assert np.asarray(got[2]).std(0).min() > 1e-3
    # and bfloat16 streams move them by rounding only
    low = hc.coefficients(x.astype(jnp.bfloat16), phi, alpha, b,
                          iters=s["iters"], eps=s["hc_eps"],
                          clamp=tuple(s["clamp"]))
    assert all(g.dtype == jnp.float32 for g in low)
    np.testing.assert_allclose(low[2], want[2], atol=5e-3)


@pytest.mark.parametrize("b_scale", [1.0, 20.0, 100.0])
def test_h_res_is_doubly_stochastic_and_finite_at_the_clamps_edges(b_scale):
    """``phi`` is scaled so that ``r`` spreads as at the published sizes
    (0.006 x sqrt(14336) = 0.72). The last normalisation is over rows, so
    rows sum to 1 to rounding; columns after 20 rounds to 1e-4 for nine
    tokens of ten under the seeded statistics, and the slowest matrices
    (entries e^3 apart in one row) to a few per cent: 20 rounds of
    Sinkhorn-Knopp, not a fault (the program's and the reference's agree
    to 2e-6). At ``b_scale`` 100 most entries of ``alpha r + b`` lie past
    the clamp of +-30 and ``exp`` spans e^60 inside one matrix."""
    s, phi, alpha, b, x = _hc_inputs(7, t=512, b_scale=b_scale)
    phi = phi * (0.72 / (MODEL["initializer_range"] * 16.0))
    _, _, h_res = hc.coefficients(x, phi, alpha, b, iters=s["iters"],
                                  eps=s["hc_eps"], clamp=tuple(s["clamp"]))
    h = np.asarray(h_res, np.float64)
    assert np.isfinite(h).all() and (h >= 0).all()
    np.testing.assert_allclose(h.sum(-1), 1.0, atol=1e-5)
    cols = np.abs(h.sum(-2) - 1.0).max(-1)
    if b_scale == 1.0:
        assert np.quantile(cols, 0.9) < 1e-4 and cols.max() < 0.05
    else:
        # the same few matrices for every token: b decides, not r
        assert (np.abs(np.asarray(b[2 * s["n"]:])) > s["clamp"][1]).mean() \
            > (0.1 if b_scale < 50 else 0.5)
        assert cols.max() < 0.3


def test_the_mixing_sums_are_float32_and_round_once():
    s, phi, alpha, b, x = _hc_inputs(4, dtype=jnp.bfloat16)
    h_pre, h_post, h_res = R.hc_maps(s, phi, alpha, b,
                                     x.astype(jnp.float32))
    f = jax.random.normal(jax.random.PRNGKey(9), (x.shape[0], s["D"]),
                          jnp.float32)
    u = hc.mix_in(h_pre, x)
    assert u.dtype == jnp.float32
    x32 = np.asarray(x, np.float64)
    np.testing.assert_allclose(
        u, np.einsum("tn,tnd->td", np.asarray(h_pre, np.float64), x32),
        atol=1e-5)
    out = hc.mix_out(h_res, h_post, x, f)
    assert out.dtype == jnp.bfloat16
    want = np.einsum("tij,tjd->tid", np.asarray(h_res, np.float64), x32) \
        + np.asarray(h_post, np.float64)[:, :, None] \
        * np.asarray(f, np.float64)[:, None, :]
    # one rounding to bfloat16: half an ulp, 2^-9 of the value
    assert (np.abs(np.asarray(out, np.float64) - want)
            <= np.abs(want) * 2.0 ** -8 + 1e-6).all()


# -- YaRN ----------------------------------------------------------------------------
def test_yarn_frequencies_at_the_published_sizes():
    """The program's frequencies against the reference's transcription of
    DeepSeek-V3's functions, and against the numbers the formula gives by
    hand: the dimensions that turn more than 32 times in 4096 positions
    keep their frequency, those that turn less than once are divided by
    64, and the softmax scale carries m^2 = 2.0047."""
    config = harness.load_json("configs", "xing4-29b-a4b.json")
    s = W.sizes(config["model"])
    y = s["yarn"]
    got = np.asarray(la.yarn_inv_freq(
        s["theta"], s["dr"], y["factor"], y["original"], y["beta_fast"],
        y["beta_slow"]), np.float64)
    inv, on_cos_sin, scale = R.rotary(s)
    np.testing.assert_allclose(got, inv, rtol=1e-6)
    f = 10000.0 ** (-np.arange(32) / 32.0)
    turns = 4096 * f / (2 * np.pi)
    assert (got[turns > 32.5] == f[turns > 32.5].astype(np.float32)).all()
    np.testing.assert_allclose(got[turns < 0.97], f[turns < 0.97] / 64,
                               rtol=1e-6)
    between = (turns < 32) & (turns > 1)
    assert ((got[between] < f[between]) & (got[between]
                                           > f[between] / 64)).all()
    assert on_cos_sin == 1.0
    m = 0.1 * math.log(64) + 1
    assert abs(m - 1.4159) < 1e-4 and abs(m * m - 2.0047) < 1e-4
    assert scale == pytest.approx(192 ** -0.5 * m * m, rel=1e-12)
    assert la.yarn_mscale(64, 1) == pytest.approx(m, rel=1e-12)
    # unscaled where nothing is stretched
    assert la.yarn_mscale(1, 1) == 1.0
    assert R.rotary(s, "yarn_off")[2] == pytest.approx(192 ** -0.5)


# -- the router's scaling factor ------------------------------------------------------
@pytest.mark.parametrize("seed", [1, 2])
def test_gates_are_scaled_by_the_routed_scaling_factor(seed):
    s = W.sizes(MODEL)
    lw = W.make(MODEL, seed).layer(3)
    z = jax.random.normal(jax.random.PRNGKey(seed), (24, s["D"]),
                          jnp.float32)
    ids1, g1 = moe.route_sigmoid_topk(z, lw["router"], lw["router_bias"], 4)
    ids1b, g1b = moe.route_sigmoid_topk(z, lw["router"], lw["router_bias"],
                                        4, 1.0)
    ids2, g2 = moe.route_sigmoid_topk(z, lw["router"], lw["router_bias"], 4,
                                      2)
    # factor 1 is the function it was: the same picks, gates that sum to 1
    np.testing.assert_array_equal(ids1, ids1b)
    np.testing.assert_array_equal(g1, g1b)
    np.testing.assert_allclose(np.asarray(g1).sum(-1), 1.0, atol=1e-6)
    np.testing.assert_array_equal(ids1, ids2)
    np.testing.assert_array_equal(np.asarray(g2), 2 * np.asarray(g1))
    ref_ids, ref_g, _ = R.route(s, lw, z)
    np.testing.assert_array_equal(np.sort(ids2, -1), np.sort(ref_ids, -1))
    np.testing.assert_allclose(np.sort(g2, -1), np.sort(ref_g, -1),
                               atol=1e-6)
    # the whole routed layer, every expert held, against the reference's
    # loop over the experts
    got, n_hit = moe.expert_layer(z, lw["e_gate"], lw["e_up"], lw["e_down"],
                                  ids2, g2, 0, 8)
    assert int(n_hit) == len(np.unique(np.asarray(ids2)))
    np.testing.assert_allclose(got, R.routed(s, lw, z), atol=2e-5)


# -- the family's costs -----------------------------------------------------------------
def test_costs_count_absorbed_ticks_and_plain_prompts():
    s = W.sizes(MODEL)
    d = C.token_forward_flops(s, 41, True) - C.token_forward_flops(
        s, 40, True)
    assert d == s["L"] * 2 * s["H"] * (2 * s["rkv"] + s["dr"])
    n = 30
    d = C.prompt_forward_flops(s, n + 1) - C.prompt_forward_flops(s, n)
    per_key = s["L"] * 2 * s["H"] * (s["dn"] + s["dr"] + s["dv"])
    assert d == per_key * (n + 1) + 2 * C._matmul_params(s)
    # the routed layers count K + shared experts of E
    dense = dict(s, first_dense=s["L"])
    assert C._matmul_params(s) - C._matmul_params(dense) == 3 * (
        s["D"] * s["E"] + 3 * s["D"] * s["FE"] * (1 + s["K"])
        - 3 * s["D"] * s["F"])
    with pytest.raises(SystemExit):
        C.train_step_flops(s, 1, 1)
