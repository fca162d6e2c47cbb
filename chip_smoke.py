#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on
the chip.

One process drives the main path through the entry points a user
calls, at the full width of GPT-2 large (36 layers, 1280 units, 20
heads x 64, MLP 5120, vocabulary 50257, 1024 positions; weights from a
seed), in phases that each print one JSON line as they end. Any
failed check raises and ends the run non-zero at once.

    python chip_smoke.py            # one TPU: device, serve, train, calibrate
    python chip_smoke.py --chips 4  # four TPUs: device, tp serving, fsdp training
    python chip_smoke.py --tiny     # CPU rehearsal of the same code at toy size

Without ``--tiny`` the script fails on anything but a TPU. The last
line of stdout is the result the driver reads:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

The compile cache lives where ``JAX_COMPILATION_CACHE_DIR`` says, else
in a fixed git-ignored directory of the checkout
(``mxnet_tpu.compile_cache.CHECKOUT_DIR``).
"""
from __future__ import annotations

import argparse
import gc
import hashlib
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as onp

import mxnet_tpu as mx
from mxnet_tpu import compile_cache, gluon, parallel, telemetry
from mxnet_tpu.gluon.model_zoo.gpt import GPTModel
from mxnet_tpu.parallel import partition
from mxnet_tpu.random_state import next_key
from mxnet_tpu.serving import GenerationEngine

#: GPT-2 large, published sizes — no width is cut
LARGE = dict(vocab_size=50257, units=1280, num_layers=36, num_heads=20,
             hidden_size=5120, max_length=1024)
#: the CPU rehearsal: same code, toy size (odd vocabulary and a head
#: count that divides by four, like the real one)
TINY = dict(vocab_size=503, units=64, num_layers=2, num_heads=4,
            hidden_size=256, max_length=128)

#: the repo's bounded-divergence contract for reduced precision
#: (tests/test_multitick.py, tests/test_quantized.py): teacher-forced
#: logits within LOGIT_BOUND of the fp32 model's, greedy agreement of
#: at least AGREE_MIN. An argmax taken from logits that are each within
#: LOGIT_BOUND of the reference lies within 2 * LOGIT_BOUND of the
#: reference's maximum.
LOGIT_BOUND = 0.25
AGREE_MIN = 0.9

PAGE_SIZE = 16
MAX_SLOTS = 8
#: sequences per training step: the compiler's memory_analysis() of the
#: GPT-2 large TrainStep program at 4 x 1024 tokens is 12.7 GB of a
#: v5e's 16 GB, Adam state included (rehearsal, PERF.md section 5)
TRAIN_BATCH = 4
SEED = 0


def emit(phase, **fields):
    print(json.dumps({"phase": phase, **fields}), flush=True)


def check(cond, what):
    if not cond:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


def digest(token_lists):
    h = hashlib.sha256()
    for toks in token_lists:
        h.update(onp.asarray(toks, "i4").tobytes())
        h.update(b"|")
    return h.hexdigest()[:16]


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------
def device_phase(tiny, chips):
    devs = jax.devices()
    d0 = devs[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devs)}
    emit("device", **device, jax=jax.__version__,
         compile_cache_dir=compile_cache.cache_dir(),
         compile_cache_entries=compile_cache.entry_count())
    check(tiny or d0.platform == "tpu",
          f"no TPU: jax runs on {d0.platform!r} (use --tiny for the "
          f"CPU rehearsal)")
    check(len(devs) == chips,
          f"expected {chips} device(s), jax sees {len(devs)}")
    return device


def build_model(cfg, seed=SEED):
    """GPT-2-style decoder at ``cfg``: Normal(0.02) weights from
    ``seed``, LM head tied to the token embedding (as GPT-2 ties it)."""
    mx.np.random.seed(seed)
    net = GPTModel(**cfg)
    net.initialize(mx.init.Normal(0.02))
    net._gen_params()             # materialize deferred shapes
    params = net.collect_params()
    params["lm_head.weight"].set_data(
        params["word_embed.weight"].data().copy())   # own buffer: donated
    return net


def make_requests(cfg):
    """Eight seeded prompts of 16-512 tokens with 32-64 new tokens
    each at 1024 positions (scaled down with a shorter context)."""
    rng = onp.random.RandomState(SEED)
    lens = [n * cfg["max_length"] // 1024
            for n in (16, 48, 100, 200, 300, 400, 512, 64)]
    news = [n * cfg["max_length"] // 1024
            for n in (32, 40, 48, 56, 64, 32, 40, 48)]
    prompts = [rng.randint(0, cfg["vocab_size"], n).astype("i4")
               for n in lens]
    return prompts, news


def serve(engine, prompts, news):
    """Submit every request, drain every stream; returns the token
    lists and the wall seconds from first submit to last token."""
    t0 = time.perf_counter()
    streams = [engine.submit(p, max_new_tokens=n)
               for p, n in zip(prompts, news)]
    results = [s.result(timeout=900) for s in streams]
    wall = time.perf_counter() - t0
    for r, n in zip(results, news):
        check(r.finish_reason == "length" and len(r.tokens) == n,
              f"request ended {r.finish_reason!r} with "
              f"{len(r.tokens)}/{n} tokens")
    return [list(map(int, r.tokens)) for r in results], wall


def teacher_forced(net, prompts, outs):
    """Run the fp32 full-sequence ``forward`` of ``net`` over prompt +
    generated tokens of two requests (a short and a long prompt) and
    hold each generated token to the contract: within ``2 *
    LOGIT_BOUND`` of the reference's best logit at its position
    everywhere, equal to the reference's argmax at ``AGREE_MIN`` of
    the positions."""
    prompts, outs = (prompts[0], prompts[3]), (outs[0], outs[3])
    pad_to = -(-max(len(p) + len(t)
                    for p, t in zip(prompts, outs)) // 64) * 64
    net.hybridize()
    rows = onp.zeros((len(prompts), pad_to), "i4")
    for i, (p, t) in enumerate(zip(prompts, outs)):
        rows[i, :len(p)] = p
        rows[i, len(p):len(p) + len(t)] = t
    # a TRUE fp32 reference: on a TPU the default precision multiplies
    # float32 matrices in bf16 passes
    with jax.default_matmul_precision("highest"):
        logits = net(mx.np.array(rows)).asnumpy()
    check(logits.dtype == onp.float32 and onp.isfinite(logits).all(),
          "teacher-forced logits not finite fp32")
    gaps, hits = [], []
    for i, (p, t) in enumerate(zip(prompts, outs)):
        at = logits[i, len(p) - 1:len(p) - 1 + len(t)]   # (n_new, V)
        gaps.extend(at.max(-1) - at[onp.arange(len(t)), t])
        hits.extend(at.argmax(-1) == onp.asarray(t))
    gap, agree = float(max(gaps)), float(onp.mean(hits))
    check(gap <= 2 * LOGIT_BOUND,
          f"a greedy token sits {gap:.3f} below the fp32 best logit "
          f"(bound {2 * LOGIT_BOUND})")
    check(agree >= AGREE_MIN,
          f"greedy agreement with fp32 teacher forcing {agree:.3f} "
          f"< {AGREE_MIN}")
    return {"max_logit_gap": gap, "greedy_agreement": agree,
            "positions": len(hits)}


def lowered_text(net, name, *args):
    """StableHLO of one of the model's paged generation programs at
    the avals of ``args`` (what ``GPTModel.decode_hlo`` does, without
    compiling) — to see that the Pallas kernel is in the program."""
    p = net._ensure_paged()
    batch = args[0].shape[0]
    return p[name].lower(
        next_key(), net._param_call_datas(p["params"]),
        net._quant_arg(), net._lora_arg(), net._lora_idx(None, batch),
        *args).as_text()


def kernels_present(engine):
    """``tpu_custom_call`` in the lowered prefill program (flash
    attention). The paged decode program carries none by design: it
    takes the compiler's gather path on every backend (PERF.md section
    6, PR 25). Skipped off the TPU (the --tiny rehearsal): there the
    same ops take their jnp paths, by design."""
    if jax.default_backend() != "tpu":
        return "skipped: not a TPU"
    net, cache = engine.model, engine._cache
    row = jnp.zeros((engine._p_max,), jnp.int32)
    width = engine.policy.sizes(engine._chunk)[0]
    count = lowered_text(
        net, "fresh", jnp.zeros((1, width), jnp.int32),
        jnp.int32(width), jnp.int32(0), row, cache,
    ).count("tpu_custom_call")
    check(count, "the lowered prefill program carries no Pallas kernel")
    return {"prefill": count}


def traces():
    return sum(telemetry.counter_value(c) for c in
               ("model.gpt.trace", "ops.sampling.trace"))


def engine_clock():
    """The engine's own host-clock histograms of the window: ms per
    decode tick and per prefill chunk (each ends in a host sync), and
    how many of each there were."""
    hists = telemetry.snapshot()["histograms"]
    return {k: {f: hists[f"serving.generate.{k}"][f]
                for f in ("count", "avg", "p50", "max")}
            for k in ("decode", "prefill", "ttft")}


def peak_bytes():
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def serve_phase(cfg, compiles):
    net = build_model(cfg)
    prompts, news = make_requests(cfg)
    t0 = time.perf_counter()
    with compile_cache.measure():
        engine = GenerationEngine(
            net, paged=True, page_size=PAGE_SIZE, max_slots=MAX_SLOTS,
            compute_dtype="bfloat16",
            max_new_tokens=max(news)).warmup()
    compile_s = time.perf_counter() - t0
    warm_traces, warm_compiles = traces(), compiles.n
    outs, wall = serve(engine, prompts, news)
    steady = {"traces": traces() - warm_traces,
              "backend_compiles": compiles.n - warm_compiles}
    check(not any(steady.values()),
          f"compiles after warm-up: {steady}")
    kernels = kernels_present(engine)
    engine.close()
    contract = teacher_forced(net, prompts, outs)
    emit("serve", model=cfg, requests=len(prompts),
         prompt_tokens=[int(p.size) for p in prompts],
         tokens_produced=sum(map(len, outs)), wall_s=wall,
         engine_ms=engine_clock(), compile_s=compile_s,
         steady_state=steady,
         tpu_custom_calls=kernels, contract=contract,
         digest=digest(outs), peak_bytes_in_use=peak_bytes(),
         compile_cache=cache_counts())


def train_batch(cfg):
    """One seeded batch of full-length sequences."""
    rng = onp.random.RandomState(SEED + 1)
    x = rng.randint(0, cfg["vocab_size"],
                    (TRAIN_BATCH, cfg["max_length"] + 1)).astype("i4")
    return mx.np.array(x[:, :-1]), mx.np.array(x[:, 1:])


def run_steps(cfg, steps, **step_kw):
    """``steps`` TrainStep steps on the SAME batch every step: the
    loss must fall."""
    net = build_model(cfg, seed=SEED + 2)
    data, label = train_batch(cfg)
    # (B, T, V) logits and (B, T) labels: the loss keeps the row axis,
    # one mean a sequence, which is what TrainStep masks and averages
    step = parallel.TrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                              "adam", {"learning_rate": 1e-4},
                              compute_dtype="bfloat16", **step_kw)
    losses, secs = [], []
    for _ in range(steps):
        t0 = time.perf_counter()
        loss = step(data, label)
        loss._data.block_until_ready()
        secs.append(time.perf_counter() - t0)
        losses.append(float(loss.asnumpy()))
    check(all(onp.isfinite(losses)), f"non-finite loss: {losses}")
    return losses, secs, step


def release():
    """Give the device back between phases: drop dead arrays and the
    loaded executables (on a TPU a loaded program holds HBM of its
    own; the persistent cache keeps the compile). Returns what is
    still held, which the next phase must fit beside."""
    gc.collect()
    jax.clear_caches()
    stats = jax.devices()[0].memory_stats() or {}
    return {"live_array_bytes": sum(a.nbytes for a in jax.live_arrays()),
            "bytes_in_use": stats.get("bytes_in_use")}


def train_phase(cfg):
    losses, secs, _ = run_steps(cfg, 3)
    check(losses[2] < losses[0],
          f"loss did not fall over three steps: {losses}")
    emit("train", model=cfg, batch=TRAIN_BATCH, losses=losses,
         first_step_s=secs[0], step_s=secs[1:],
         peak_bytes_in_use=peak_bytes(), compile_cache=cache_counts())


def calibrate_phase(n, iters):
    """One large bf16 matmul chain timed to ``block_until_ready`` and
    again to a host fetch of one element: where the two agree,
    ``block_until_ready`` really blocks on this backend."""
    # x @ x == x exactly: every entry stays 2**-13 (n = 8192)
    x = jnp.full((n, n), 1.0 / n, jnp.bfloat16)
    mm = jax.jit(lambda a, b: jnp.dot(a, b))

    def chain():
        y = x
        for _ in range(iters):
            y = mm(y, x)
        return y

    float(chain()[0, 0])                       # compile both programs
    t0 = time.perf_counter()
    chain().block_until_ready()
    block_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    val = float(chain()[0, 0])
    fetch_s = time.perf_counter() - t0
    check(abs(val * n - 1.0) < 1e-2, f"matmul chain value {val}")
    flops = 2.0 * n ** 3 * iters
    emit("calibrate", n=n, iters=iters, block_until_ready_s=block_s,
         host_fetch_s=fetch_s,
         tflops_block=flops / block_s / 1e12,
         tflops_fetch=flops / fetch_s / 1e12)


# -- four chips ---------------------------------------------------------------
def tp_serve_phase(cfg):
    """The same model and prompts through a one-device engine and a
    ``mesh_layout="tp"`` engine over all four devices (fp32: bf16
    compute does not compose with a mesh yet; mesh engines trace the
    attention ops on their jnp paths — no Pallas kernels here)."""
    prompts, news = make_requests(cfg)
    # half the default pool: fp32 pages are twice the bytes of the
    # one-chip phase's bf16 ones, and the one-device engine must fit
    # one chip (the requests need a quarter of the default pool)
    kw = dict(paged=True, page_size=PAGE_SIZE, max_slots=MAX_SLOTS,
              n_pages=MAX_SLOTS * cfg["max_length"] // PAGE_SIZE // 2 + 1,
              max_new_tokens=max(news))
    ref_net = build_model(cfg)
    engine = GenerationEngine(ref_net, **kw).warmup()
    ref_outs, ref_wall = serve(engine, prompts, news)
    ref_pool = partition.per_device_bytes(
        [engine._cache["k"], engine._cache["v"]])
    engine.close()
    del engine
    emit("released", **release())

    mesh = parallel.make_mesh((1, 4), ("dp", "tp"))
    net = build_model(cfg)
    engine = GenerationEngine(net, mesh_layout="tp", mesh=mesh,
                              **kw).warmup()
    outs, wall = serve(engine, prompts, news)
    pool = partition.per_device_bytes(
        [engine._cache["k"], engine._cache["v"]])
    # shards, not tokens: every parameter lives on all four devices
    # and the head/mlp-sharded ones hold a quarter each
    n_split = 0
    for name, p in net.collect_params().items():
        arr = p.data()._data
        shards = arr.addressable_shards
        check({s.device for s in shards} == set(jax.devices()),
              f"{name} is not on all four devices")
        n_split += shards[0].data.size * 4 == arr.size
    check(n_split >= 6 * cfg["num_layers"],
          f"only {n_split} parameters are split four ways")
    check(abs(pool / ref_pool - 0.25) < 0.02,
          f"KV pool bytes per device {pool} vs one-device {ref_pool}")
    engine.close()
    first_diff = next(
        ((i, j) for i, (a, b) in enumerate(zip(ref_outs, outs))
         for j, (x, y) in enumerate(zip(a, b)) if x != y), None)
    contract = teacher_forced(ref_net, prompts, outs)
    emit("tp_serve", model=cfg, requests=len(prompts),
         tokens_produced=sum(map(len, outs)),
         one_device={"digest": digest(ref_outs), "wall_s": ref_wall,
                     "kv_pool_bytes_per_device": ref_pool},
         tp4={"digest": digest(outs), "wall_s": wall,
              "kv_pool_bytes_per_device": pool,
              "params_split_four_ways": int(n_split)},
         first_difference=first_diff, contract=contract,
         kernels="none: mesh engines trace under jnp_only()")


def fsdp_train_phase(cfg):
    """Two ``TrainStep`` steps under the fsdp layout over four devices
    against the same two steps on one device."""
    ref_losses, _, ref_step = run_steps(cfg, 2)
    del ref_step
    emit("released", **release())
    mesh = parallel.make_mesh((4,), ("dp",))
    losses, secs, step = run_steps(cfg, 2, mesh=mesh,
                                   layout="fsdp")
    colls = partition.hlo_collectives(
        step.compiled_hlo(*train_batch(cfg)))
    check(colls.get("all-gather", {}).get("count", 0) > 0,
          f"no all-gather in the fsdp step: {colls}")
    worst = max(abs(a - b) for a, b in zip(ref_losses, losses))
    check(worst < 0.15, f"fsdp losses {losses} vs one device "
                        f"{ref_losses}")
    emit("fsdp_train", model=cfg, batch=TRAIN_BATCH,
         one_device_losses=ref_losses, fsdp_losses=losses,
         max_loss_difference=worst, step_s=secs,
         collectives={k: v["count"] for k, v in colls.items()})


# ---------------------------------------------------------------------------
class CompileCounter:
    """Counts XLA backend compiles through jax.monitoring."""

    def __init__(self):
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1


def cache_counts():
    return {k: int(telemetry.counter_value(f"compile_cache.{k}"))
            for k in ("hit", "miss")} | {
                "entries": compile_cache.entry_count()}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tiny", action="store_true",
                    help="CPU rehearsal at toy size (not what the "
                         "driver runs)")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: the tensor-parallel serving and fsdp "
                         "training comparison, and nothing else")
    args = ap.parse_args(argv)
    if args.tiny and args.chips == 4 \
            and os.environ.get("JAX_PLATFORMS") == "cpu":
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=4")

    compile_cache.configure(compile_cache.CHECKOUT_DIR)
    compiles = CompileCounter()
    device = device_phase(args.tiny, args.chips)

    cfg = TINY if args.tiny else LARGE
    if args.chips == 4:
        tp_serve_phase(cfg)
        emit("released", **release())
        fsdp_train_phase(cfg)
    else:
        serve_phase(cfg, compiles)
        emit("released", **release())
        train_phase(cfg)
        calibrate_phase(*((256, 4) if args.tiny else (8192, 32)))
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
