#!/usr/bin/env python3
"""On the chip: how far ``SDARModel``'s served tokens lie from the plain
reference's replay, and what the controls and the planted faults read
(PERF.md section 6, the limits file of the family's cell).

    python3 scripts/sdar_chip_check.py float32 positions

``float32``: the program at the published widths in float32 (two layers,
the whole vocabulary: what a float32 chip holds) serves three requests
through ``GenerationEngine`` (prompts of 1301, 700 and 3 tokens: chunks of
512, a prompt tail in the first block, one shorter than a block; pages of
64, the kernels as the chip runs them) and the reference replays each
(``chipbench/families/sdar/reference.py``, float32 at
``Precision.HIGHEST``): with the program computing the reference's
function, every served token is the replay's argmax, at a gap of rounding.

``positions``: the cell's own configuration (bfloat16, six layers) serves
requests of the mix's sizes, and each served token is judged by the
replay: its gap, the ranking margins of its block up to its step and its
row's router margin; the same for the two control precisions and each of
``reference.FAULTS`` on the same requests. Prints the widest gap and the
widest request mean of each, over all positions and under a grid of
margin rules (positions whose margins are at least so much), and keeps
every position's readings in
``chiprun_out/sdar_positions.json``: the readings the cell's
``served_logit_gap`` (a request's mean gap) is set from.

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

import numpy as np  # noqa: E402

from chipbench import harness, program as chip_program  # noqa: E402
from chipbench.families.sdar import (  # noqa: E402
    program as P, reference as R, weights as W)
from chipbench.generators import closed_loop  # noqa: E402

OUT = os.path.join(ROOT, "chiprun_out")


def serve(net, serve_args, requests):
    """Every request through one engine at once; the tokens served."""
    eng = chip_program.build_engine(net, serve_args)
    try:
        streams = [eng.submit(p, max_new_tokens=n) for p, n in requests]
        return [np.asarray(s.result(timeout=900).tokens, np.int32)
                for s in streams]
    finally:
        eng.close()


def float32(cfg):
    import jax
    # the program's float32 products at full precision, as the reference's
    jax.config.update("jax_default_matmul_precision", "highest")
    model = dict(cfg["model"], num_hidden_layers=2)
    serve_args = dict(cfg["serve"], compute_dtype="float32",
                      max_new_tokens=64)
    net = P.build_model(model, 3300000101, dtype="float32",
                        max_length=serve_args["max_length"])
    rng = np.random.default_rng(5)
    requests = [(rng.integers(0, model["vocab_size"], p).astype(np.int32),
                 n) for p, n in ((1301, 48), (700, 61), (3, 40))]
    served = serve(net, serve_args, requests)
    del net
    chip_program.release()
    w = W.make(model, 3300000101)
    for (prompt, n), toks in zip(requests, served):
        g, h = R.served_gaps(model, w, prompt, toks, 64)
        print(f"float32 {len(prompt)} + {n}: served {len(toks)}, widest "
              f"gap {g.max():.2e}, argmax at {h.mean():.4f} of tokens",
              flush=True)


def positions(cfg, seed=3300000107, n_req=6):
    model = cfg["model"]
    mix = harness.load_json("traffic", "closed32-blockdiff-chat.json")
    serve_args = dict(cfg["serve"],
                      max_new_tokens=int(mix["new_tokens"]["max"]))
    pairs = closed_loop.sizes_of(mix)
    rng = np.random.default_rng(seed)
    picks = rng.permutation(len(pairs))[:n_req]
    requests = [(rng.integers(0, model["vocab_size"], pairs[i][0])
                 .astype(np.int32), pairs[i][1]) for i in picks]
    t0 = time.perf_counter()
    net = P.build_model(model, seed)
    served = serve(net, serve_args, requests)
    print(f"positions: served {n_req} requests in "
          f"{time.perf_counter() - t0:.1f} s", flush=True)
    del net
    chip_program.release()
    w = W.make(model, seed)
    t_pad = R.PAD_LONG
    keep = []
    for (prompt, n), toks in zip(requests, served):
        row = {"p": len(prompt), "n": len(toks)}
        base = R.replay(w, prompt, toks, t_pad)
        sel = base["judged"]
        row["rank"] = base["rank_margin"][sel].tolist()
        row["route"] = base["route_margin"][sel].tolist()
        row["step"] = base["step"][sel].tolist()
        row["program"] = (base["best"][sel] - base["mine"][sel]).tolist()
        for c in ("int8", "fp8") + R.FAULTS:
            got = R.replay(w, prompt, toks, t_pad, c)
            ctoks = got["argmax"][got["judged"]][:len(toks)]
            ref = R.replay(w, prompt, toks, t_pad, judge=ctoks)
            row[c] = (ref["best"][sel] - ref["mine"][sel]).tolist()
        keep.append(row)
        print(f"positions {len(prompt)} + {len(toks)}: program widest "
              f"{max(row['program']):.4f} mean {np.mean(row['program']):.4f}"
              + "".join(f", {c} {max(row[c]):.3f}"
                        for c in ("int8", "fp8") + R.FAULTS), flush=True)
    os.makedirs(OUT, exist_ok=True)
    with open(os.path.join(OUT, "sdar_positions.json"), "w") as f:
        json.dump(keep, f)
    for rank in (0.0, 0.005, 0.01, 0.02, 0.05):
        for route in (0.0, 0.005, 0.01, 0.02):
            line = []
            for c in ("program", "int8", "fp8") + R.FAULTS:
                widest, mean, judged = 0.0, 0.0, 0
                for row in keep:
                    ok = (np.asarray(row["rank"]) >= rank) \
                        & (np.asarray(row["route"]) >= route)
                    judged += int(ok.sum())
                    if ok.any():
                        g = np.asarray(row[c])[ok]
                        widest = max(widest, float(g.max()))
                        mean = max(mean, float(g.mean()))
                line.append(f"{c} {widest:.4f}/{mean:.4f}")
            print(f"rule rank>={rank} route>={route}: judged {judged} "
                  f"(widest / widest request mean): "
                  + ", ".join(line), flush=True)


def main(argv):
    cfg = harness.load_json("configs", "sdar-30b-a3b-chat.json")
    chip_program.configure_compile_cache()
    if "float32" in argv:
        float32(cfg)
    if "positions" in argv:
        positions(cfg)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
