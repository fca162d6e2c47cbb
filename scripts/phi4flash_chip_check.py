#!/usr/bin/env python3
"""On the chip: how far ``Phi4FlashModel``'s served logits lie from the
plain reference, by cause (PERF.md section 6, PR 31).

    chiprun -- python3 scripts/phi4flash_chip_check.py float32 bfloat16
    chiprun -- python3 scripts/phi4flash_chip_check.py faults

``float32`` / ``bfloat16``: the programs at the published widths and a cut
depth (8 layers, a vocabulary of 32768) serve two requests through
``GenerationEngine`` (1300 + 200 and 700 + 500 tokens: chunk boundaries at
512, the rings' wrap at 1024, pages of 64, the scan kernel as the chip
runs it), every served row of logits is kept, and each is compared with
the reference's row (``chipbench/families/phi4flash/reference.py``,
float32 at ``Precision.HIGHEST``). In float32 at full matmul precision the
two agree to rounding (1.3e-5, my chip run, PR 31), so chunks, rings,
pages, states and the kernel compute what the reference does; in bfloat16
the same model moves a logit by 0.11, which is what ``served_logit_gap``
of the family's cell reads ten times the other cells' for.

``faults``: each of ``reference.FAULTS`` at the whole depth and widths,
through the reference alone, on two sequences of random ids: the readings
beside the limit in ``chipbench/limits/``.

Prints one line a reading and writes all of them to
``chiprun_out/phi4flash_chip_check.json``. Not a test: it needs the chip
for the kernel and the matmul precision it checks.
"""
from __future__ import annotations

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import numpy as np  # noqa: E402

from chipbench import harness, program as chip_program  # noqa: E402
from chipbench.families.phi4flash import (  # noqa: E402
    program as P, reference as R, weights as W)
from mxnet_tpu.serving import GenerationEngine  # noqa: E402

SEED, VOCAB, LAYERS, PAD = 77, 32768, 8, 2560
REQUESTS = ((1300, 200), (700, 500))


def serve_keeping_logits(net, requests, dtype):
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
    with GenerationEngine(net, max_slots=4, max_length=PAD, paged=True,
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
    small = dict(cfg["model"], num_hidden_layers=LAYERS, vocab_size=VOCAB)
    rng = np.random.default_rng(31)
    requests = [(rng.integers(0, VOCAB, p).astype(np.int32), n)
                for p, n in REQUESTS]
    R.PAD_LONG = PAD
    t0 = time.time()
    # the engine traces in its worker thread, and the context manager
    # jax.default_matmul_precision is thread-local: set it for the process
    jax.config.update("jax_default_matmul_precision",
                      "highest" if dtype == "float32" else "default")
    net = P.build_model(small, SEED, dtype=dtype)
    served = serve_keeping_logits(net, requests, dtype)
    del net
    chip_program.release()
    w = W.make(small, SEED)
    for i, ((prompt, n), (tokens, rows)) in enumerate(zip(requests, served)):
        seq = np.zeros((PAD,), np.int32)
        seq[:len(prompt)] = prompt
        seq[len(prompt):len(prompt) + n] = tokens
        ref = np.asarray(R.logits_rows(small, w, seq, len(prompt) - 1, n))
        diff = np.abs(rows - ref).max(-1)
        served_logit = ref[np.arange(n), np.asarray(tokens)]
        out[f"{dtype}_{i}"] = {
            "logit_diff_max": float(diff.max()),
            "logit_diff_median": float(np.median(diff)),
            "served_logit_gap": float((ref.max(-1) - served_logit).max()),
            "greedy_agreement": float(
                (ref.argmax(-1) == np.asarray(tokens)).mean()),
            "logit_std": float(ref.std()),
            "seconds": round(time.time() - t0, 1)}
        print(dtype, i, json.dumps(out[f"{dtype}_{i}"]), flush=True)


def faults(cfg, out):
    model = cfg["model"]
    w = W.make(model, 2900000031)
    R.PAD_LONG = 5120
    rng = np.random.default_rng(5)
    for p, n in ((1505, 400), (3000, 300)):
        prompt = rng.integers(0, model["vocab_size"], p).astype(np.int32)
        cont = rng.integers(0, model["vocab_size"], n).astype(np.int32)
        for fault in R.FAULTS:
            t0 = time.time()
            gaps, _ = R.served_gaps(model, w, prompt, cont, 1024,
                                    control=fault)
            out[f"fault_{fault}_{p}"] = {
                "served_logit_gap": float(gaps.max()),
                "seconds": round(time.time() - t0, 1)}
            print(fault, p, json.dumps(out[f"fault_{fault}_{p}"]),
                  flush=True)


def main(argv):
    chip_program.configure_compile_cache()
    cfg = harness.load_json("configs", "phi4-mini-flash-reasoning.json")
    out = {"device": jax.devices()[0].device_kind}
    for dtype in ("float32", "bfloat16"):
        if dtype in argv:
            cut_depth(cfg, dtype, out)
    if "faults" in argv:
        faults(cfg, out)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out",
                           "phi4flash_chip_check.json"), "w") as f:
        json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
