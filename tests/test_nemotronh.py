"""The ``nemotron_h`` decoder family at a toy size that keeps the published
pattern ``MEMEM*EME``: hidden 64, Mamba-2 mixers of 8 heads x 8 channels
in 2 groups of 16 states (chunks of 8), 16 routed experts of which 6 a
token and a shared one, held as rank 0 of two (experts 0-7), gates scaled
by 2.5, 4 query heads over 2 K/V heads with no positions.

The system (``NemotronHModel`` through ``GenerationEngine``: chunked
prefill, then decode through the paged pool and the recurrent states) is
compared with the plain float32 reference of ``chipbench/families/
nemotronh/`` on the benchmark's seeded weights: logits, not tokens.
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
from mxnet_tpu.gluon.model_zoo import nemotronh as nh  # noqa: E402
from mxnet_tpu.ops import moe, ssm  # noqa: E402
from mxnet_tpu.serving import GenerationEngine  # noqa: E402

from chipbench.families.nemotronh import costs as C  # noqa: E402
from chipbench.families.nemotronh import program as P  # noqa: E402
from chipbench.families.nemotronh import reference as R  # noqa: E402
from chipbench.families.nemotronh import weights as W  # noqa: E402

CHUNK, PAGE, S_MAX = 8, 4, 64
MODEL = dict(
    vocab_size=512, hidden_size=64, num_hidden_layers=9,
    hybrid_override_pattern="MEMEM*EME", num_attention_heads=4,
    num_key_value_heads=2, head_dim=16, mamba_num_heads=8, mamba_head_dim=8,
    n_groups=2, ssm_state_size=16, conv_kernel=4, chunk_size=8,
    moe_intermediate_size=32, moe_shared_expert_intermediate_size=48,
    n_routed_experts=8, ep_size=2, ep_rank=0, num_experts_per_tok=6,
    routed_scaling_factor=2.5, n_group=1, topk_group=1, norm_topk_prob=True,
    mlp_hidden_act="relu2", mamba_hidden_act="silu", use_conv_bias=True,
    n_shared_experts=1, norm_eps=1e-5, initializer_range=0.1,
    time_step_min=0.001, time_step_max=0.1, time_step_floor=1e-4)
SEED = 39
#: float32 leaves against the float32 reference: rounding only (the order
#: of a sum; the chunked recurrence against the stepped one)
TOL32 = 5e-5
#: bfloat16 leaves, pool, tails and stream, where the reference's own
#: router margin at a position is at least ``MARGIN``: nine layers of
#: bfloat16 move a logit by a few hundredths there
MARGIN, TOL16 = 0.002, 0.12


def build(dtype="bfloat16"):
    """The toy model as ``families/nemotronh/program.build_model`` builds
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
    row = np.zeros((S_MAX,), np.int32)
    row[:len(seq)] = seq
    logits, margin = R.logits_rows(
        MODEL, W.make(MODEL, SEED), row, len(prompt) - 1, len(tokens),
        control, margins=True)
    return np.asarray(logits), np.asarray(margin)


#: (prompt, new): fresh in one bucket; chunks across a chunk boundary (8)
#: and page boundaries (4) with a ragged last chunk, several times over
SHAPES = [(5, 6), (21, 8), (38, 14)]


def _prompt(n):
    return np.random.default_rng(n).integers(
        0, MODEL["vocab_size"], n).astype(np.int32)


@pytest.fixture(scope="module")
def served32(net32):
    return serve(net32, [(_prompt(n), k) for n, k in SHAPES])


@pytest.mark.parametrize("case", range(len(SHAPES)))
def test_engine_logits_follow_the_reference(served32, case):
    """Prefill in chunks, then decode through the pool and the states,
    against the reference's one full forward pass: every logit of every
    served position."""
    n_prompt, n_new = SHAPES[case]
    tokens, rows = served32[case]
    assert len(tokens) == n_new
    ref, _ = reference_rows(_prompt(n_prompt), tokens)
    assert np.abs(rows - ref).max() <= TOL32
    assert tokens == [int(r.argmax()) for r in ref]


def test_bfloat16_follows_the_reference_where_its_picks_are_wide(net):
    """The model as it is served (bfloat16 leaves, pool, tails and
    stream). A position is compared where the reference's own router
    margin is wide: at this size one flipped expert moves a logit by more
    than bfloat16 does."""
    prompt = _prompt(38)
    (tokens, rows), = serve(net, [(prompt, 14)])
    ref, margin = reference_rows(prompt, tokens)
    gap = np.abs(rows - ref).max(-1)
    wide = margin >= MARGIN
    assert wide.sum() >= 3, margin
    assert gap[wide].max() <= TOL16, (gap, margin)


def test_forward_equals_prefill_then_decode(net32, served32):
    tokens, rows = served32[2]
    n = SHAPES[2][0]
    seq = np.concatenate([_prompt(n), tokens]).astype(np.int32)
    whole = np.asarray(net32(mx.np.array(seq[None]))._data)[0]
    assert np.abs(whole[n - 1:n - 1 + len(tokens)] - rows).max() <= TOL32


@pytest.mark.parametrize("fault", R.FAULTS)
def test_a_planted_fault_moves_every_served_row(served32, fault):
    """Each broken mechanism moves the reference's logits at the served
    positions far past the float32 tolerance: the comparison that decides
    ``correct`` can see it."""
    tokens, _ = served32[2]
    prompt = _prompt(SHAPES[2][0])
    ref, _ = reference_rows(prompt, tokens)
    broken, _ = reference_rows(prompt, tokens, control=fault)
    assert np.abs(broken - ref).max() > 100 * TOL32


def test_served_gaps_read_a_request_by_its_mean_gap(served32):
    """Every served token is judged, and each reports its request's mean
    gap under the reference's best logit: the float32 program's own tokens
    read 0, and the last token moved to the reference's worst choice
    (no position reads it) raises the mean by that token's gap over the
    request's length."""
    tokens, _ = served32[2]
    prompt = _prompt(SHAPES[2][0])
    w = W.make(MODEL, SEED)
    gaps, hits = R.served_gaps(MODEL, w, prompt, tokens, len(tokens))
    assert gaps.shape == (len(tokens),) and hits.all()
    assert np.abs(gaps).max() <= 1e-6
    ref, _ = reference_rows(prompt, tokens)
    worst = list(tokens)
    worst[-1] = int(ref[-1].argmin())
    gaps, hits = R.served_gaps(MODEL, w, prompt, worst, len(tokens))
    want = (ref[-1].max() - ref[-1].min()) / len(tokens)
    np.testing.assert_allclose(gaps, want, rtol=1e-4)
    assert hits.sum() == len(tokens) - 1


# -- the recurrence ----------------------------------------------------------
def _ssd_inputs(t, seed=0, h=8, p=8, g=2, n=16):
    rng = np.random.default_rng(seed)
    softplus = np.logaddexp(0.0, rng.normal(size=(t, h)) - 2.0)
    arrays = (rng.normal(size=(t, h, p)), softplus,
              -rng.uniform(1.0, 16.0, h), 0.5 * rng.normal(size=(t, g, n)),
              0.5 * rng.normal(size=(t, g, n)), np.linspace(0.5, 1.5, h),
              rng.normal(size=(h, p, n)))
    return tuple(jnp.asarray(v, jnp.float32) for v in arrays)


@pytest.mark.parametrize("t,n_valid,chunk", [(32, 27, 8), (24, 24, 8),
                                             (16, 5, 16)],
                         ids=["padded", "whole", "one-block"])
def test_ssd_chunk_scan_follows_the_stepped_recurrence(t, n_valid, chunk):
    """The Pallas kernel in interpret mode, the ``lax.scan`` of the
    recurrence and the decode tick's step taken position by position
    agree, from a carried-in state and with a padded tail: the state
    handed on is the one after ``n_valid`` positions."""
    x, dt, a, b, c, d, h0 = _ssd_inputs(t)
    dtm = jnp.where((jnp.arange(t) < n_valid)[:, None], dt, 0.0)
    y_k, s_k = jax.jit(lambda *v: ssm.ssd_scan_pallas(
        *v, chunk, interpret=True))(x, dtm, a, b, c, d, h0)
    y_s, s_s = jax.jit(ssm.ssd_scan)(x, dt, a, b, c, d, h0,
                                     jnp.int32(n_valid))
    step = jax.jit(ssm.ssd_step)
    state, ys = h0[None], []
    for i in range(n_valid):
        y, state = step(x[i][None], dt[i][None], a, b[i][None], c[i][None],
                        d, state, jnp.ones((1,), bool))
        ys.append(y[0])
    y_t = np.stack(ys)
    scale = float(np.abs(y_t).max())
    for y, s in ((y_k, s_k), (y_s, s_s)):
        np.testing.assert_allclose(np.asarray(y)[:n_valid], y_t,
                                   atol=2e-6 * scale)
        np.testing.assert_allclose(s, state[0], atol=2e-6
                                   * float(np.abs(state).max()))


def test_ssd_step_leaves_an_inactive_row_as_it_came():
    x, dt, a, b, c, d, h0 = _ssd_inputs(2, seed=3)
    h = jnp.stack([h0, 2.0 * h0])
    _, new = jax.jit(ssm.ssd_step)(x, dt, a, b, c, d, h,
                                   jnp.array([True, False]))
    np.testing.assert_array_equal(new[1], h[1])
    assert np.abs(np.asarray(new[0] - h[0])).max() > 1e-3


def test_grouped_gated_norm_by_hand():
    rng = np.random.default_rng(2)
    y, z = rng.normal(size=(3, 12)), rng.normal(size=(3, 12))
    g = rng.normal(size=(12,))
    got = nh.grouped_gated_norm(jnp.asarray(y, jnp.float32),
                                jnp.asarray(z, jnp.float32),
                                jnp.asarray(g, jnp.float32), 3, 1e-5)
    v = y * z / (1.0 + np.exp(-z))
    want = np.empty_like(v)
    for i in range(3):
        part = v[:, 4 * i:4 * i + 4]
        want[:, 4 * i:4 * i + 4] = part / np.sqrt(
            (part ** 2).mean(-1, keepdims=True) + 1e-5)
    np.testing.assert_allclose(got, want * g, atol=1e-5)


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
    before = jax.tree.map(lambda a: np.asarray(a).copy(), cache)
    _, cache = net32.decode_step_paged(
        np.array([5, 9], np.int32), np.array([1, 0], np.int32), cache)
    for key in ("ssm", "conv"):
        for new, was in zip(cache[key], before[key]):
            np.testing.assert_array_equal(np.asarray(new)[1], was[1])
            assert np.abs(np.asarray(new)[0] - was[0]).max() > 0
    for key in ("k", "v"):
        np.testing.assert_array_equal(np.asarray(cache[key][0])[_pages(1)],
                                      before[key][0][_pages(1)])
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
    names = ["model.nemotronh." + n for n in (
        "trace", "keys_attended", "ssd_token_layers.prefill",
        "ssd_token_layers.decode", "experts_hit.decode")]
    with engine(net) as eng:
        eng.warmup()
        c0 = {n: telemetry.counter_value(n) for n in names}
        for n in (5, 9, 30):
            eng.submit(np.arange(n, dtype=np.int32) + 1,
                       max_new_tokens=4).result(timeout=300)
        got = {n.split("nemotronh.")[1]: telemetry.counter_value(n) - c0[n]
               for n in names}
    assert got["trace"] == 0
    # three decode ticks a request through one attention layer: contexts
    # 6-8, 10-12, 31-33, each counted with the token's own position
    assert got["keys_attended"] == (6 + 7 + 8 + 10 + 11 + 12
                                    + 31 + 32 + 33)
    # four Mamba-2 layers a row: every prompt token, every decoded token
    assert got["ssd_token_layers.prefill"] == 4 * (5 + 9 + 30)
    assert got["ssd_token_layers.decode"] == 4 * 9
    # nine ticks, four routed layers, both slots' rows of six picks over
    # the 8 held (a tick routes every row, live or not); the last calls'
    # counts may still be on the device
    assert 0 < got["experts_hit.decode"] <= 9 * 4 * 8


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


# -- expert parallelism: the share ---------------------------------------------
def test_the_two_ranks_and_the_shared_expert_add_up_to_the_whole_layer():
    """Every expert held (``ep_size`` 1, 16 experts) is the uncut layer of
    the reference; rank 0 and rank 1 of ``ep_size`` 2 each compute their
    8 experts' part of the same routing, and with the shared expert,
    which every rank computes alike, counted once they give it."""
    whole = dict(MODEL, n_routed_experts=16, ep_size=1)
    s = W.sizes(whole)
    lw = W.make(whole, SEED).layer(1)
    z = jnp.asarray(np.random.default_rng(4).normal(size=(24, s["D"])),
                    jnp.float32)
    want = jax.jit(lambda lw, z: R.experts(s, lw, z))(lw, z)
    rank_part = jax.jit(lambda p, z, lo: moe.routed_layer(
        z, z, p, s["top_k"], lo, s["gate_scale"], up_t=True)[0],
        static_argnums=2)
    parts = [rank_part(dict(lw, e_up=lw["e_up"][lo:lo + 8],
                            e_down=lw["e_down"][lo:lo + 8]), z, lo)
             for lo in (0, 8)]
    shared = moe.relu2(z @ lw["s_up"]) @ lw["s_down"]
    np.testing.assert_allclose(parts[0] + parts[1] + shared, want,
                               atol=1e-4 * float(np.abs(want).max()))
    # each rank's part alone is not the layer
    assert np.abs(np.asarray(parts[0] + shared - want)).max() > 1e-2


# -- the family's costs ---------------------------------------------------------
def test_costs_count_held_picks_keys_and_the_recurrence():
    s = W.sizes(MODEL)
    d = C.token_forward_flops(s, 41, True) - C.token_forward_flops(
        s, 40, True)
    assert d == 4 * s["Hq"] * s["dh"]
    n = 30
    d = C.prompt_forward_flops(s, n + 1) - C.prompt_forward_flops(s, n)
    assert d == C._row_flops(s) + 4 * s["Hq"] * s["dh"] * (n + 1)
    # a routed layer counts 3 of its 6 picks: half the experts are held
    routed = C._mixer_params(s, W.EXPERTS)
    assert routed == s["D"] * 16 + 2 * s["D"] * (32 * 3 + 48)
    f = {"sizes": s, "prompts_done": [10, 30]}
    flops, nbytes = C.ssd_chunk_scan_call(f)
    assert flops == 40 * 4 * 4 * 8 * 8 * 16
    assert nbytes == 4 * (160 * (2 * 64 + 8 + 2 * 32) + 8 * 2 * 1024)
    with pytest.raises(SystemExit):
        C.train_step_flops(s, 1, 1)
