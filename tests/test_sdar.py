"""``SDARModel`` at toy size on the CPU: generation by diffusion over
blocks through ``GenerationEngine``'s paged path, held to the plain
reference of ``chipbench/families/sdar/reference.py`` with seeded
weights.

The float32 model installs the reference's own values (those bfloat16
holds), so its forward agrees with the reference to rounding and its
served tokens are the literal generation's, token for token."""
import os
import sys

import jax
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from mxnet_tpu import telemetry  # noqa: E402
from mxnet_tpu.ops import moe  # noqa: E402
from mxnet_tpu.serving import GenerationEngine  # noqa: E402
from mxnet_tpu.serving.generate import block_schedule  # noqa: E402

from chipbench.families.sdar import program as P  # noqa: E402
from chipbench.families.sdar import reference as R  # noqa: E402
from chipbench.families.sdar import weights as W  # noqa: E402

CHUNK, PAGE, S_MAX = 16, 8, 96
MODEL = dict(
    vocab_size=512, hidden_size=64, num_hidden_layers=2,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    num_experts=8, num_experts_per_tok=2, moe_intermediate_size=32,
    norm_topk_prob=True, rope_theta=1000000, rms_norm_eps=1e-6,
    mask_token_id=511, block_length=4, denoise_steps=2,
    remasking_strategy="low_confidence_static", initializer_range=0.1,
    mlp_only_layers=[], decoder_sparse_step=1, tie_word_embeddings=False,
    attention_bias=False)
SEED = 41
#: float32 leaves against the float32 reference: rounding only
TOL32 = 5e-5
#: (prompt, new tokens): prompts a multiple of the block and not, one
#: shorter than a block, one over a chunk; budgets a multiple and not
SHAPES = [(3, 9), (8, 8), (13, 10), (21, 7), (37, 12)]


def build(dtype="float32"):
    return P.build_model(MODEL, SEED, dtype=dtype, max_length=S_MAX)


def engine(net, slots=2, **more):
    args = dict(max_slots=slots, max_length=S_MAX, paged=True,
                page_size=PAGE, prefill_chunk=CHUNK, prefix_cache=False,
                compute_dtype=net.generation_support["compute_dtype"][0],
                max_new_tokens=16)
    args.update(more)
    return GenerationEngine(net, **args)


def _prompt(n, seed=0):
    return np.random.default_rng([seed, n]).integers(
        0, MODEL["vocab_size"], n).astype(np.int32)


@pytest.fixture(scope="module")
def net32():
    return build()


@pytest.fixture(scope="module")
def weights():
    return W.make(MODEL, SEED)


@pytest.fixture(scope="module")
def served32(net32):
    """Every shape through one engine of two slots (so slots are reused
    and co-tenants sit at other steps), submitted together."""
    with engine(net32).warmup() as eng:
        streams = [eng.submit(_prompt(p), max_new_tokens=n)
                   for p, n in SHAPES]
        return [s.result(timeout=300) for s in streams]


def test_forward_matches_the_reference(net32, weights):
    toks = _prompt(30)
    got = np.asarray(net32(toks[None]).asnumpy())[0]
    want = np.asarray(R.forward(weights, toks))
    assert np.abs(got - want).max() < TOL32 * max(1.0, np.abs(want).max())


def test_the_mask_is_block_causal_not_causal(weights):
    """A row sees the rest of its own block (a later position in the block
    moves it) and nothing of a later block."""
    toks = _prompt(16)
    base = np.asarray(R.forward(weights, toks))
    later_in_block = toks.copy()
    later_in_block[6] = (toks[6] + 1) % 511       # block 1 = positions 4-7
    moved = np.asarray(R.forward(weights, later_in_block))
    assert np.abs(moved[4] - base[4]).max() > 1e-3     # 6 is seen by 4
    assert np.abs(moved[:4] - base[:4]).max() == 0     # block 0 is not
    causal = np.asarray(R.forward(weights, toks, control="causal_mask"))
    assert np.abs(causal[:4] - base[:4]).max() > 1e-3


@pytest.mark.parametrize("case", range(len(SHAPES)))
def test_engine_serves_the_literal_generation(served32, weights, case):
    p, n = SHAPES[case]
    res = served32[case]
    assert res.finish_reason == "length" and len(res.tokens) == n
    assert list(res.tokens) == list(R.generate(weights, _prompt(p), n))


@pytest.mark.parametrize("case", range(len(SHAPES)))
def test_batched_replay_equals_the_literal_generation(weights, case):
    """``served_gaps`` replays in ``steps + 1`` forwards what ``generate``
    does in one forward a step: on the literal generation's own tokens
    every one is the replay's argmax, at a gap of rounding."""
    p, n = SHAPES[case]
    prompt = _prompt(p, seed=1)
    toks = R.generate(weights, prompt, n)
    gaps, hits = R.served_gaps(MODEL, weights, prompt, toks, 16)
    assert len(gaps) == n and hits.all()
    assert gaps.max() < TOL32


def test_served_logits_are_the_reference_rows(net32, weights):
    """The denoising program's logits at every step of two blocks (the
    first opening with a prompt tail of two) are the reference forward's
    rows of the block as it stands, its prefix the final tokens; and the
    positions it unmasks are the most confident masked ones."""
    prompt = _prompt(10)
    seen = []
    real = net32._logits

    def spy(top, x):
        out = real(top, x)
        seen.append(np.asarray(out))
        return out

    cache = net32.init_paged_cache(2, 2 * S_MAX // PAGE + 1, PAGE, S_MAX)
    row = np.zeros((S_MAX // PAGE,), "i4")
    row[:2] = [1, 2]
    _, cache = net32.prefill_paged(prompt[:8][None], 8, 0, row, cache,
                                   start=0, fresh=True)
    b, blk, mask = 2, 4, MODEL["mask_token_id"]
    f_blk = np.zeros((b, blk), "i4")
    f_blk[0, :2] = prompt[8:]
    f_fill = np.zeros((b, blk), bool)
    f_fill[0, :2] = True
    table = np.stack([row, np.zeros_like(row)])
    prefix, start = list(prompt[:8]), 8
    toks, fill = list(prompt[8:]) + [0, 0], [True, True, False, False]
    sched, step = block_schedule(2, 2), 0
    net32._logits = spy
    try:
        with jax.disable_jit():
            for i in range(4):
                ids = prefix + [t if f else mask for t, f in zip(toks, fill)]
                tok, cache = net32.denoise_step_paged(
                    np.array([sched[step], 0], "i4"),
                    np.array([i == 0, 0], "i4"),
                    f_blk, f_fill, np.array([8, 0], "i4"), table, cache)
                got = seen[-1].reshape(b, blk, -1)[0]
                want = np.asarray(R.forward(
                    weights, np.asarray(ids, np.int32)))[start:start + blk]
                assert np.abs(got - want).max() \
                    < TOL32 * max(1, np.abs(want).max()), i
                # log-confidence: the best logit less the logsumexp
                conf = -np.log(np.exp(
                    want - want.max(-1, keepdims=True)).sum(-1))
                order = sorted((j for j in range(blk) if not fill[j]),
                               key=lambda j: (-conf[j], j))
                for j in order[:sched[step]]:
                    toks[j], fill[j] = int(want[j].argmax()), True
                assert list(np.asarray(tok)[0]) == toks, i
                step += 1
                if all(fill):
                    prefix += toks
                    start += blk
                    toks, fill = [0] * blk, [False] * blk
                    sched, step = block_schedule(blk, 2), 0
    finally:
        net32._logits = real
    assert len(seen) == 4 and start == 16
    assert prefix[10:] == list(R.generate(weights, prompt, 6))


@pytest.mark.parametrize("fault", R.FAULTS)
def test_a_planted_fault_reads_over_the_program(served32, weights, fault):
    """Each planted fault of the reference reads a gap the float32 program
    does not: the judged tokens of a fault are its own replay's picks."""
    worst = 0.0
    for (p, _), res in zip(SHAPES, served32):
        g, _ = R.served_gaps(MODEL, weights, _prompt(p), res.tokens, 16,
                             control=fault)
        worst = max(worst, float(g.max()))
    assert worst > 0.05, fault


def test_an_eos_inside_a_block_ends_the_request_there(net32, weights):
    prompt = _prompt(9)
    full = list(R.generate(weights, prompt, 12))
    # an id whose first place is inside a block, not at its end
    at = next(i for i in range(len(full)) if (9 + i) % 4 != 3
              and full[i] not in full[:i])
    with engine(net32) as eng:
        res = eng.submit(prompt, max_new_tokens=12,
                         eos_id=full[at]).result(timeout=300)
    assert res.finish_reason == "eos"
    assert list(res.tokens) == full[:at + 1]


def test_a_reused_slot_reads_nothing_of_the_last_tenant(net32):
    """One slot, four requests one after the other: each is what it is
    on an engine of its own."""
    alone = []
    for p, n in SHAPES[:4]:
        with engine(net32, slots=1) as eng:
            alone.append(eng.submit(_prompt(p), max_new_tokens=n)
                         .result(timeout=300).tokens)
    with engine(net32, slots=1) as eng:
        again = [eng.submit(_prompt(p), max_new_tokens=n)
                 .result(timeout=300).tokens for p, n in SHAPES[:4]]
    assert again == alone


def test_no_retrace_across_slots_at_other_steps(net32):
    """After warm-up, slots entering at any step of any block, with
    prompt tails of every length, run the compiled programs."""
    with engine(net32, slots=2).warmup() as eng:
        before = telemetry.counter_value("model.sdar.trace")
        streams = [eng.submit(_prompt(p, seed=2), max_new_tokens=n)
                   for p, n in ((5, 11), (6, 3), (7, 9), (17, 6), (2, 5))]
        for s in streams:
            s.result(timeout=300)
        assert telemetry.counter_value("model.sdar.trace") == before


def test_the_counters_count_blocks_and_tokens(net32):
    names = ("serving.generate.tokens", "serving.generate.blocks_committed",
             "serving.generate.denoise_steps", "serving.generate.dispatches",
             "serving.generate.host_syncs", "serving.generate.prefills",
             "serving.generate.prefill_chunks", "model.sdar.tokens_unmasked")
    with engine(net32, slots=1) as eng:
        eng.warmup()
        for _, n in net32._pending:      # the warm-up's own device counts
            n.block_until_ready()
        net32._count()
        c0 = {n: telemetry.counter_value(n) for n in names}
        # 9 prompt tokens: one block of 8 prefilled, a tail of 1; 10 new:
        # blocks 8-11 (3 masked: 2 steps), 12-15 and 16-19 (2 steps each,
        # the last one cut at the budget after 3 tokens)
        res = eng.submit(_prompt(9), max_new_tokens=10).result(timeout=300)
        eng._drain_ahead()
        # the model's device counts reach their counters a call later
        for _, n in net32._pending:
            n.block_until_ready()
        net32._count()
        got = {n: telemetry.counter_value(n) - c0[n] for n in names}
    assert len(res.tokens) == 10
    assert got["serving.generate.tokens"] == 10
    assert got["serving.generate.blocks_committed"] == 3
    assert got["serving.generate.denoise_steps"] == 6
    assert got["serving.generate.dispatches"] == 6
    assert got["serving.generate.host_syncs"] == 3
    assert got["serving.generate.prefills"] == 1
    assert got["serving.generate.prefill_chunks"] == 1
    assert got["model.sdar.tokens_unmasked"] == 3 + 4 + 4


def test_block_schedule_is_llada_s():
    assert block_schedule(4, 2) == [2, 2]
    assert block_schedule(3, 2) == [2, 1]
    assert block_schedule(1, 2) == [1]
    assert block_schedule(5, 3) == [2, 2, 1]
    assert R.block_schedule(3, 2) == block_schedule(3, 2)


def test_the_engine_refuses_what_the_family_does_not_take(net32):
    with pytest.raises(ValueError, match="decode_ticks"):
        engine(net32, decode_ticks=2)
    with pytest.raises(ValueError, match="prefix_cache"):
        engine(net32, prefix_cache=True)
    with engine(net32) as eng:
        with pytest.raises(TypeError, match="greedily"):
            eng.submit(_prompt(5), temperature=0.7)


def test_the_model_refuses_another_unmasking_rule():
    with pytest.raises(ValueError, match="remasking_strategy"):
        P.build_model(dict(MODEL, remasking_strategy="low_confidence_dynamic"),
                      SEED, dtype="float32", max_length=S_MAX)


@pytest.mark.parametrize("k", [1, 2, 3, 4])
def test_a_step_unmasks_the_count_it_is_handed(net32, k):
    """The engine counts ``k_s`` from the static schedule; the step
    unmasks that many of the block's masked positions, and a slot handed
    0 stands still."""
    prompt = _prompt(8)
    cache = net32.init_paged_cache(2, 2 * S_MAX // PAGE + 1, PAGE, S_MAX)
    row = np.zeros((S_MAX // PAGE,), "i4")
    row[:1] = [1]
    _, cache = net32.prefill_paged(prompt[None], 8, 0, row, cache,
                                   start=0, fresh=True)
    blk = MODEL["block_length"]
    tok, cache = net32.denoise_step_paged(
        np.array([k, 0], "i4"), np.array([1, 0], "i4"),
        np.zeros((2, blk), "i4"), np.zeros((2, blk), bool),
        np.array([8, 0], "i4"), np.stack([row, np.zeros_like(row)]), cache)
    filled = np.asarray(cache["fill"])
    done = k == blk
    assert int(filled[0].sum()) == (0 if done else k)
    assert int(np.asarray(cache["commit"])[0]) == int(done)
    assert int(np.asarray(cache["start"])[0]) == (8 + blk if done else 8)
    assert not filled[1].any() and not np.asarray(tok)[1].any()


def test_route_softmax_topk_matches_a_plain_loop():
    rng = np.random.default_rng(3)
    z = rng.standard_normal((7, 16)).astype(np.float32)
    w = rng.standard_normal((16, 10)).astype(np.float32)
    ids, gates = moe.route_softmax_topk(z, w, 3)
    raw_ids, raw = moe.route_softmax_topk(z, w, 3, norm_topk_prob=False)
    for t in range(7):
        logits = z[t].astype(np.float64) @ w
        p = np.exp(logits - logits.max())
        p /= p.sum()
        order = sorted(range(10), key=lambda e: -p[e])[:3]
        assert list(np.asarray(ids[t])) == order == list(np.asarray(
            raw_ids[t]))
        np.testing.assert_allclose(np.asarray(raw[t]), p[order], rtol=1e-5)
        np.testing.assert_allclose(np.asarray(gates[t]),
                                   p[order] / p[order].sum(), rtol=1e-5)


def test_parameter_count_and_bytes_held(net32):
    s = W.sizes(MODEL)
    assert net32.parameter_count() == W.parameter_count(s)
    net16 = build("bfloat16")
    held = sum(p.data()._data.nbytes
               for p in net16.collect_params().values())
    wide = s["L"] * s["D"] * s["E"]          # the float32 routers
    assert held == 2 * W.parameter_count(s) + 2 * wide

