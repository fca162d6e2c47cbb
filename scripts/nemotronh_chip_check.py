#!/usr/bin/env python3
"""On the chip: how far ``NemotronHModel``'s served logits lie from the
plain reference, by cause (PERF.md section 6).

    python3 scripts/nemotronh_chip_check.py kernel float32 bfloat16
    python3 scripts/nemotronh_chip_check.py faults positions

``kernel``: the Pallas kernel ``ssd_chunk_scan`` against the ``lax.scan``
of the recurrence on the chip, at the published widths (64 heads of 64
channels, 8 groups of 128 states): a 512-position chunk of 4 blocks and a
64-position bucket, each with a carried-in state and a padded tail.

``float32`` / ``bfloat16``: the programs at the published widths (the
float32 one at a cut depth, ``MEMEM*E``; the bfloat16 one at the cell's,
``MEMEM*EME``; a vocabulary of 16384) serve three requests through
``GenerationEngine`` (1300 + 200, 700 + 400 and 200 + 300 tokens: chunk
boundaries at 512, pages of 64, the kernels as the chip runs them), every
served row of logits is kept, and each is compared with the reference's
row (``chipbench/families/nemotronh/reference.py``, float32 at
``Precision.HIGHEST``). In float32 at full matmul precision the two agree
to rounding; in bfloat16 the difference is what ``served_logit_gap`` of
the family's cell reads a position by, beside the share of positions
where the reference's own narrowest router margin is narrow.

``faults``: each of ``reference.FAULTS`` and the two control precisions at
the cell's depth and widths, through the reference alone, on two
sequences of random ids: the request's mean gap, which is what
``served_logit_gap`` of the family's cell reads a request by.

``positions``: the cell's own configuration (bfloat16, every layer, the
whole held vocabulary) serves ten requests of the mix's sizes, and each
served position's logits row is compared with the reference's: the
program's distance from it (a correct program's rows lie within ~0.07,
or far away in the bursts a flipped pick leaves), and the request's mean
gap for the program, the two control precisions and each fault on the
same positions: the readings ``served_logit_gap`` is set from.

Prints one line a reading. Not a test: it runs on a TPU, for the kernels
and the matmul precision it checks.
"""
from __future__ import annotations

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from chipbench import harness, program as chip_program  # noqa: E402
from chipbench.families.nemotronh import (  # noqa: E402
    program as P, reference as R, weights as W)
from mxnet_tpu.ops import ssm  # noqa: E402
from mxnet_tpu.serving import GenerationEngine  # noqa: E402

SEED, VOCAB, PAD = 77, 16384, 2048
#: the depth each dtype is served at: float32 leaves take twice the bytes
PATTERN = {"float32": "MEMEM*E", "bfloat16": "MEMEM*EME"}
REQUESTS = ((1300, 200), (700, 400), (200, 300))
NARROW = 2e-3


def kernel(cfg, out):
    s = W.sizes(cfg["model"])
    h, p, g, n = s["H"], s["P"], s["G"], s["N"]
    for t, n_valid in ((512, 450), (64, 64)):
        k = jax.random.split(jax.random.PRNGKey(t), 6)
        x = jax.random.normal(k[0], (t, h, p))
        dt = jax.nn.softplus(jax.random.normal(k[1], (t, h)) - 3.0)
        a = -jax.random.uniform(k[2], (h,), minval=1.0, maxval=16.0)
        b = 0.5 * jax.random.normal(k[3], (t, g, n))
        c = 0.5 * jax.random.normal(k[4], (t, g, n))
        h0 = jax.random.normal(k[5], (h, p, n))
        d = jnp.ones((h,))
        live = jnp.arange(t) < n_valid
        dtm = jnp.where(live[:, None], dt, 0.0)
        y0, s0 = jax.jit(ssm._ssd_jnp)(x, dtm, a, b, c, d, h0)
        y1, s1 = jax.jit(lambda *v: ssm.ssd_scan_pallas(*v, s["chunk"]))(
            x, dtm, a, b, c, d, h0)
        y0, y1 = np.asarray(y0)[:n_valid], np.asarray(y1)[:n_valid]
        out[f"kernel_{t}"] = {
            "y_diff_max": float(np.abs(y0 - y1).max()),
            "y_max": float(np.abs(y0).max()),
            "state_diff_max": float(np.abs(np.asarray(s0)
                                           - np.asarray(s1)).max()),
            "state_max": float(np.abs(np.asarray(s0)).max())}
        print("kernel", t, json.dumps(out[f"kernel_{t}"]), flush=True)


def serve_keeping_logits(net, requests, dtype, max_length=PAD):
    """Each request's served tokens and the logits row of every one."""
    rows = {}
    prefill, decode = net.prefill_paged, net.decode_step_paged

    def spy_prefill(tokens, n_valid, slot, pages, cache, **kw):
        logits, cache = prefill(tokens, n_valid, slot, pages, cache, **kw)
        rows[int(slot)] = [np.asarray(logits)[0]]
        return logits, cache

    def spy_decode(tokens, active, cache):
        logits, cache = decode(tokens, active, cache)
        for b in np.flatnonzero(np.asarray(active)):
            rows[int(b)].append(np.asarray(logits)[b])
        return logits, cache

    net.prefill_paged, net.decode_step_paged = spy_prefill, spy_decode
    out = []
    with GenerationEngine(net, max_slots=4, max_length=max_length, paged=True,
                          page_size=64, prefill_chunk=512,
                          prefix_cache=False, compute_dtype=dtype,
                          max_new_tokens=1024) as eng:
        for prompt, n in requests:
            rows.clear()
            got = eng.submit(prompt, max_new_tokens=n).result(timeout=900)
            kept = next(v for v in rows.values() if len(v) >= n)
            out.append((list(got.tokens), np.stack(kept[:n])))
    return out


def cut_depth(cfg, dtype, out):
    small = dict(cfg["model"], num_hidden_layers=len(PATTERN[dtype]),
                 hybrid_override_pattern=PATTERN[dtype], vocab_size=VOCAB)
    rng = np.random.default_rng(33)
    requests = [(rng.integers(0, VOCAB, p).astype(np.int32), n)
                for p, n in REQUESTS]
    t0 = time.time()
    # the engine traces in its worker thread, and the context manager
    # jax.default_matmul_precision is thread-local: set it for the process
    jax.config.update("jax_default_matmul_precision",
                      "highest" if dtype == "float32" else "default")
    net = P.build_model(small, SEED, dtype=dtype, max_length=PAD)
    served = serve_keeping_logits(net, requests, dtype)
    del net
    chip_program.release()
    jax.config.update("jax_default_matmul_precision", "default")
    w = W.make(small, SEED)
    for i, ((prompt, n), (tokens, rows)) in enumerate(zip(requests, served)):
        seq = np.zeros((PAD,), np.int32)
        seq[:len(prompt)] = prompt
        seq[len(prompt):len(prompt) + n] = tokens
        ref, margin = R.logits_rows(small, w, seq, len(prompt) - 1, n,
                                    margins=True)
        ref, margin = np.asarray(ref), np.asarray(margin)
        diff = np.abs(rows - ref).max(-1)
        gap = ref.max(-1) - ref[np.arange(n), np.asarray(tokens)]
        narrow = margin < NARROW
        out[f"{dtype}_{i}"] = {
            "logit_diff_max": float(diff.max()),
            "logit_diff_median": float(np.median(diff)),
            "served_logit_gap": float(gap.max()),
            "rows_with_a_narrow_margin": float(narrow.mean()),
            "logit_diff_max_wide": float(diff[~narrow].max(initial=0)),
            "served_logit_gap_wide": float(gap[~narrow].max(initial=0)),
            "served_logit_gap_mean": float(gap.mean()),
            "greedy_agreement": float(
                (ref.argmax(-1) == np.asarray(tokens)).mean()),
            "logit_std": float(ref.std()),
            "seconds": round(time.time() - t0, 1)}
        # how the readings fall as the margin a position needs grows
        for cut in (0.004, 0.008, 0.016, 0.032):
            at = margin >= cut
            out[f"{dtype}_{i}"][f"at_margin_{cut}"] = [
                int(at.sum()), float(gap[at].max(initial=0)),
                float(diff[at].max(initial=0))]
        print(dtype, i, json.dumps(out[f"{dtype}_{i}"]), flush=True)


def faults(cfg, out):
    model = cfg["model"]
    jax.config.update("jax_default_matmul_precision", "default")
    w = W.make(model, 2900000039)
    rng = np.random.default_rng(5)
    for p, n in ((300, 400), (1500, 500)):
        prompt = rng.integers(0, model["vocab_size"], p).astype(np.int32)
        cont = rng.integers(0, model["vocab_size"], n).astype(np.int32)
        for fault in R.FAULTS + ("int8", "fp8"):
            t0 = time.time()
            gaps, _ = R.served_gaps(model, w, prompt, cont, n,
                                    control=fault)
            out[f"fault_{fault}_{p}"] = {
                "served_logit_gap": float(gaps.max()),
                "seconds": round(time.time() - t0, 1)}
            print(fault, p, json.dumps(out[f"fault_{fault}_{p}"]),
                  flush=True)


def positions(cfg, out, seed=3900000201, n_req=10):
    from chipbench.generators import closed_loop
    model = cfg["model"]
    sizes = closed_loop.sizes_of(harness.load_json("traffic",
                                                   "closed64-chat.json"))
    rng = np.random.default_rng([seed, 11])
    requests = [(rng.integers(0, model["vocab_size"], sizes[i][0]).astype(
        np.int32), sizes[i][1]) for i in rng.permutation(len(sizes))[:n_req]]
    jax.config.update("jax_default_matmul_precision", "default")
    net = P.build_model(model, seed, max_length=R.PAD_LONG)
    served = serve_keeping_logits(net, requests, "bfloat16", R.PAD_LONG)
    del net
    chip_program.release()
    w = W.make(model, seed)
    for i, ((prompt, n), (tokens, rows)) in enumerate(zip(requests, served)):
        seq = np.zeros((R.PAD_LONG,), np.int32)
        seq[:len(prompt)] = prompt
        seq[len(prompt):len(prompt) + n] = tokens
        ref = np.asarray(R.logits_rows(model, w, seq, len(prompt) - 1, n))
        diff = np.abs(rows - ref).max(-1)
        row = {"served": n, "diff_median": float(np.median(diff)),
               "diff_over_0.4": float((diff > 0.4).mean())}
        picks = {"program": np.asarray(tokens)}
        for control in ("int8", "fp8") + R.FAULTS:
            picks[control] = np.asarray(R.logits_rows(
                model, w, seq, len(prompt) - 1, n, control)).argmax(-1)
        for name, pick in picks.items():
            gap = ref.max(-1) - ref[np.arange(n), pick]
            row[f"mean_gap_{name}"] = float(gap.mean())
        out[f"positions_{i}"] = row
        print("positions", i, json.dumps(row), flush=True)


def main(argv):
    chip_program.configure_compile_cache()
    cfg = harness.load_json("configs", "nemotron3-nano-30b-a3b.json")
    print("device", jax.devices()[0].device_kind, flush=True)
    out = {}
    if "kernel" in argv:
        kernel(cfg, out)
    for dtype in ("float32", "bfloat16"):
        if dtype in argv:
            cut_depth(cfg, dtype, out)
    if "faults" in argv:
        faults(cfg, out)
    if "positions" in argv:
        positions(cfg, out)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
