#!/usr/bin/env python3
"""On the chip: how far ``Xing4Model``'s served logits lie from the plain
reference, by cause (PERF.md section 6, PR 33).

    chiprun -- python3 scripts/xing4_chip_check.py float32 bfloat16 maps
    chiprun -- python3 scripts/xing4_chip_check.py bfloat16 layers=6
    chiprun -- python3 scripts/xing4_chip_check.py faults

``float32`` / ``bfloat16``: the programs at the published widths and a cut
depth (both dense layers and one routed layer with all 64 experts, a
vocabulary of 32768) serve two requests through ``GenerationEngine``
(1300 + 200 and 700 + 500 tokens: chunk boundaries at 512, pages of 64,
the expert kernel as the chip runs it), every served row of logits is
kept, and each is compared with the reference's row
(``chipbench/families/xing4/reference.py``, float32 at
``Precision.HIGHEST``). In float32 at full matmul precision the two agree
to rounding, so chunks, pages, the absorbed form, the hyper-connections
and the expert layer compute what the reference does; in bfloat16 the
difference is what ``served_logit_gap`` of the family's cell reads, and
the readings say where it comes from: the rows are split by the
reference's own narrowest router margin at their position (a bfloat16
stream moves a sigmoid score by some 1e-4, so a pick whose margin is
narrower than ``NARROW`` may fall the other way in any bfloat16 program).
``layers=N`` takes another depth (2: the dense layers alone, no router).

``maps``: one hyper-connection's three maps from bfloat16 streams, the
program's function on the chip against float64 arithmetic on the host: a
float32 product with a converted bfloat16 operand rounds both on a TPU
(PERF.md section 7 (v)), and this is the path that must not.

``faults``: each of ``reference.FAULTS`` at the cell's depth and widths,
through the reference alone, on two sequences of random ids: the readings
beside the limit in ``chipbench/limits/``.

Prints one line a reading and writes all of them to
``chiprun_out/xing4_chip_check.json``. Not a test: it needs the chip for
the kernel and the matmul precision it checks.
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
from chipbench.families.xing4 import (  # noqa: E402
    program as P, reference as R, weights as W)
from mxnet_tpu.ops import hyper_connection  # noqa: E402
from mxnet_tpu.serving import GenerationEngine  # noqa: E402

SEED, VOCAB, LAYERS, PAD = 77, 32768, 3, 2560
REQUESTS = ((1300, 200), (700, 500))
NARROW = 2e-3


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


def cut_depth(cfg, dtype, out, layers):
    small = dict(cfg["model"], num_hidden_layers=layers, vocab_size=VOCAB)
    rng = np.random.default_rng(33)
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
        ref, margin = R.logits_rows(small, w, seq, len(prompt) - 1, n,
                                    margins=True)
        ref, margin = np.asarray(ref), np.asarray(margin)
        diff = np.abs(rows - ref).max(-1)
        served_logit = ref[np.arange(n), np.asarray(tokens)]
        gap = ref.max(-1) - served_logit
        narrow = margin < NARROW
        out[f"{dtype}_{layers}_{i}"] = {
            "logit_diff_max": float(diff.max()),
            "logit_diff_median": float(np.median(diff)),
            "logit_diff_p99": float(np.quantile(diff, 0.99)),
            "served_logit_gap": float(gap.max()),
            "rows_with_a_narrow_margin": float(narrow.mean()),
            "logit_diff_max_narrow": float(diff[narrow].max(initial=0)),
            "logit_diff_max_wide": float(diff[~narrow].max(initial=0)),
            "served_logit_gap_wide": float(gap[~narrow].max(initial=0)),
            "greedy_agreement": float(
                (ref.argmax(-1) == np.asarray(tokens)).mean()),
            "logit_std": float(ref.std()),
            "seconds": round(time.time() - t0, 1)}
        print(dtype, layers, i, json.dumps(out[f"{dtype}_{layers}_{i}"]),
              flush=True)


def maps(cfg, out):
    """The three maps of 32 and of 512 tokens' bfloat16 streams."""
    jax.config.update("jax_default_matmul_precision", "default")
    s = W.sizes(cfg["model"])
    lw = W.make(cfg["model"], SEED).layer(2, for_program=True)
    phi, alpha, b = (np.asarray(lw[k], np.float64)
                     for k in ("a_phi", "a_alpha", "a_b"))
    n = s["n"]
    fn = jax.jit(lambda x: hyper_connection.coefficients(
        x, lw["a_phi"], lw["a_alpha"], lw["a_b"], iters=s["iters"],
        eps=s["hc_eps"], clamp=tuple(s["clamp"])))
    for t in (32, 512):
        x = (0.05 * jax.random.normal(jax.random.PRNGKey(t),
                                      (t, n, s["D"]))).astype(jnp.bfloat16)
        got = [np.asarray(g, np.float64) for g in fn(x)]
        flat = np.asarray(x, np.float64).reshape(t, -1)
        xn = flat / np.sqrt((flat ** 2).mean(-1, keepdims=True)
                            + s["hc_eps"])
        pqr = xn @ phi.T
        sig = lambda v: 1.0 / (1.0 + np.exp(-v))  # noqa: E731
        h_pre = sig(alpha[0] * pqr[:, :n] + b[:n])
        h_post = 2 * sig(alpha[1] * pqr[:, n:2 * n] + b[n:2 * n])
        m = np.exp(np.clip(alpha[2] * pqr[:, 2 * n:] + b[2 * n:],
                           *s["clamp"])).reshape(t, n, n)
        for _ in range(s["iters"]):
            m = m / (m.sum(-2, keepdims=True) + s["hc_eps"])
            m = m / (m.sum(-1, keepdims=True) + s["hc_eps"])
        out[f"maps_{t}"] = {
            name: float(np.abs(g - w).max()) for name, g, w in zip(
                ("h_pre", "h_post", "h_res"), got, (h_pre, h_post, m))}
        # what rounding x~ to bfloat16 before the product would cost
        lost = np.asarray(jnp.asarray(xn, jnp.bfloat16), np.float64) \
            @ np.asarray(jnp.asarray(phi, jnp.bfloat16), np.float64).T
        out[f"maps_{t}"]["pqr_if_rounded"] = float(np.abs(lost - pqr).max())
        out[f"maps_{t}"]["pqr_std"] = float(pqr.std())
        print("maps", t, json.dumps(out[f"maps_{t}"]), flush=True)


def faults(cfg, out):
    model = cfg["model"]
    jax.config.update("jax_default_matmul_precision", "default")
    w = W.make(model, 2900000033)
    R.PAD_LONG = 4608
    rng = np.random.default_rng(5)
    for p, n in ((1505, 400), (3900, 300)):
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


def main(argv):
    chip_program.configure_compile_cache()
    cfg = harness.load_json("configs", "xing4-29b-a4b.json")
    out = {"device": jax.devices()[0].device_kind}
    layers = [int(a.split("=")[1]) for a in argv
              if a.startswith("layers=")] or [LAYERS]
    for dtype in ("float32", "bfloat16"):
        if dtype in argv:
            for n in layers:
                cut_depth(cfg, dtype, out, n)
    if "maps" in argv:
        maps(cfg, out)
    if "faults" in argv:
        faults(cfg, out)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out",
                           "xing4_chip_check.json"), "w") as f:
        json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
