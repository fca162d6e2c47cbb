"""Traffic kind ``train``: one job of ``batch`` sequences of ``sequence``
tokens a step, a fresh seeded batch every step, fed through the normal
``mx.np.array`` -> ``TrainStep.__call__`` path.

Set-up builds ONE step object, drives it from the seed through its first
three steps by the window's own call and feed (reading, between them,
what ``correct`` compares), and hands that same object to the window. The
window's loop fetches step k-1's loss after it has dispatched step k, as
a trainer that logs its loss does, and blocks on the last; it closes when
the step in flight at ``--seconds`` completes, so the rate is all the
steps over all the time and has no step-sized quantum.
"""
from __future__ import annotations

import statistics
import time

import numpy as np

from chipbench import harness

FOLLOWED = 2          # full steps the reference follows; the third step's
#                       loss is a forward pass on the weights after two


def batch_of(seed, k, job, vocab):
    """Step ``k``'s batch, (B, S + 1) ids: every row differs."""
    rng = np.random.default_rng([int(seed), 3, int(k)])
    return rng.integers(0, vocab, (int(job["batch"]),
                                   int(job["sequence"]) + 1)).astype(np.int32)


def _worst_leaf(prog, ref, keep=None):
    """The widest gap between the program's norm of a leaf and the
    reference's, against the reference's norm of that leaf or of the
    median leaf, whichever is larger. ``keep`` masks leaves out."""
    names = [n for n in ref if keep is None or keep[n]]
    med = statistics.median(ref[n] for n in names)
    worst, at = 0.0, None
    for n in names:
        gap = abs(prog[n] - ref[n]) / max(ref[n], med)
        if not gap <= worst:                  # NaN is the worst
            worst, at = gap, n
    return worst, at


def _flat(stacked_norms, index):
    """Stacked per-kind norms -> ``{program leaf name: float}``."""
    host = {n: np.asarray(a) for n, a in stacked_norms.items()}
    return {name: float(host[kind] if layer is None else host[kind][layer])
            for name, (kind, layer) in index.items()}


def run(ctx):
    import jax
    import jax.numpy as jnp
    from chipbench import program
    model, job = ctx.config["model"], ctx.mix
    weights = harness.family(ctx.config, "weights")
    s = weights.sizes(model)
    targs = ctx.config["train"]
    hp = dict(targs["optimizer_params"])
    tokens_per_step = int(job["batch"]) * int(job["sequence"])

    net = harness.family(ctx.config, "program").build_model(
        model, ctx.seed)
    step = program.build_train_step(net, targs)
    ctx.part("weights_and_model")
    names = None

    def dispatch(k):
        return step(*program.feed(batch_of(ctx.seed, k, job, s["V"])))

    norms_of = jax.jit(lambda xs: [jnp.sqrt(jnp.sum(jnp.square(
        x.astype(jnp.float32)))) for x in xs])

    index = harness.family(ctx.config, "reference").leaf_index(model)

    @jax.jit
    def change_norms(cur, w0):
        # sliced inside the program: a second cut copy of the weights
        # would not fit beside the step's own state
        out = []
        for n, x in zip(names, cur):
            kind, layer = index[n]
            ref = w0[kind] if layer is None else w0[kind][layer]
            out.append(jnp.sqrt(jnp.sum(jnp.square(x - ref))))
        return out

    # -- the first steps: what `correct` compares -------------------------
    prog = {"loss": []}
    for k in range(3):
        prog["loss"].append(program.loss_value(dispatch(k)))
        ctx.part(f"step_{k + 1}")
        if k == 0:
            names = program.step_leaf_names(step)
            b1 = float(hp["beta1"])
            g = norms_of(program.step_first_moments(step))
            prog["grad"] = {n: float(x) / (1.0 - b1)
                            for n, x in zip(names, g)}
        if k == FOLLOWED - 1:
            w0 = weights.make(model, ctx.seed)
            cur = program.step_params(step)
            d = change_norms([cur[n] for n in names], w0)
            prog["change"] = {n: float(x) for n, x in zip(names, d)}
            del w0, d, cur

    ctx.part("readings")
    warm_compiles = ctx.compiles.n
    setup_s = ctx.mark_setup_done()

    # -- the window --------------------------------------------------------
    next_k = [3]
    losses = []

    def pipeline(enough):
        """Steps until ``enough(done_at)``: step k-1's loss is fetched
        after step k is dispatched, the last one is blocked on. Returns
        the completion stamps."""
        done_at = []
        pending = dispatch(next_k[0])
        while not (done_at and enough(done_at)):
            next_k[0] += 1
            nxt = dispatch(next_k[0])
            losses.append(program.loss_value(pending))
            done_at.append(time.perf_counter())
            pending = nxt
        next_k[0] += 1
        losses.append(program.loss_value(pending))
        done_at.append(time.perf_counter())
        return done_at

    # With --trace 1 the profiler covers the first steps; stopping it
    # stalls this thread, which is the one that dispatches, so the window's
    # own counts start once it has stopped.
    tw = traced = None
    seconds = ctx.seconds
    if ctx.trace:
        tw = harness.TracedWindow(ctx.keep_trace)
        ta = tw.start()
        n = len(pipeline(lambda d: len(d) + 1 >= int(job["trace_steps"])))
        traced = {"seconds": tw.stop() - ta, "steps": n}
        seconds = max(seconds - traced["seconds"], 1.0)
    t0 = time.perf_counter()
    done_at = pipeline(lambda d: d[-1] - t0 >= seconds)
    win = done_at[-1] - t0
    in_window = ctx.compiles.n - warm_compiles
    peak = harness.memory_peak_bytes(ctx.chips)
    steps = len(done_at)
    e2e = {"train_tokens_per_s": steps * tokens_per_step / win,
           "setup_s": setup_s}
    facts = {"seconds": win, "steps": steps, "family": ctx.config["family"],
             "sizes": s, "peaks": ctx.peaks, "chips": ctx.chips,
             "batch": int(job["batch"]), "sequence": int(job["sequence"]),
             "tokens": steps * tokens_per_step,
             "step_ms": [1e3 * (b - a) for a, b in
                         zip([t0] + done_at, done_at)]}
    if traced is not None:
        facts["traced"] = dict(facts, seconds=traced["seconds"],
                               steps=traced["steps"],
                               tokens=traced["steps"] * tokens_per_step)
    failed = sum(1 for x in losses if not np.isfinite(x))

    del step, net
    program.release()
    trace = tw.reduce() if tw is not None else None
    checks = [harness.Check("compiles_in_window", in_window, 0),
              harness.Check("nonfinite_losses", failed, 0)]
    checks += check_steps(ctx, model, job, hp, prog)
    return {"e2e": e2e, "facts": facts, "trace": trace,
            "attempted": len(losses), "failed": failed, "checks": checks,
            "memory_peak_bytes": peak}


def follow(ctx, model, job, hp, lowp=None, half_batch=False):
    """The reference's first steps from the seed: the three losses, the
    first gradient's norm and the two-step change's norm of every leaf,
    and the leaves whose change is compared. ``lowp`` is the control;
    ``half_batch`` plants the fault "half of the batch left out, the mean
    taken over the rest" in the reference."""
    import jax.numpy as jnp
    from chipbench import adam
    weights = harness.family(ctx.config, "weights")
    reference = harness.family(ctx.config, "reference")
    vocab = weights.sizes(model)["V"]
    hyper = dict(lr=float(hp["learning_rate"]), b1=float(hp["beta1"]),
                 b2=float(hp["beta2"]), eps=float(hp["epsilon"]))
    index = reference.leaf_index(model)

    def xy(k):
        b = batch_of(ctx.seed, k, job, vocab)
        if half_batch:
            b = b[:b.shape[0] // 2]
        return jnp.asarray(b[:, :-1]), jnp.asarray(b[:, 1:])

    w = weights.make(model, ctx.seed)
    loss1, g1 = reference.loss_and_grads(model, w, *xy(0), lowp)
    grad = _flat(reference.leaf_norms(g1), index)
    w = adam.adam_first(w, g1, **hyper)
    loss2, g2 = reference.loss_and_grads(model, w, *xy(1), lowp)
    w, change = adam.adam_second(w, g1, g2,
                                 leaf_norms=reference.leaf_norms, **hyper)
    del g1, g2
    change = _flat(change, index)
    loss3 = reference.loss_only(model, w, *xy(2), lowp)
    out = {"loss": [float(loss1), float(loss2), float(loss3)],
           "grad": grad, "change": change}
    del w
    # leaves whose gradient is nought to rounding move by round-off alone
    med = statistics.median(grad.values())
    out["moves"] = {n: grad[n] >= 1e-3 * med for n in grad}
    return out


def compare(prog, ref):
    """The numbers compared, as ``{name: (value, leaf or None)}``."""
    out = {f"loss{k + 1}_gap": (abs(prog["loss"][k] - ref["loss"][k]), None)
           for k in range(3)}
    out["grad_norm_gap"] = _worst_leaf(prog["grad"], ref["grad"])
    out["change_norm_gap"] = _worst_leaf(prog["change"], ref["change"],
                                         keep=ref["moves"])
    return out


def check_steps(ctx, model, job, hp, prog):
    ref = follow(ctx, model, job, hp)
    got = compare(prog, ref)
    for name, (_, leaf) in got.items():
        if leaf is not None:
            ctx.note(name + "_at", leaf)
    ctx.note("compared", {n: v for n, (v, _) in got.items()})
    ctx.note("reference_losses", ref["loss"])
    ctx.note("program_losses", prog["loss"])
    ctx.note("leaves_not_compared",
             sorted(n for n, m in ref["moves"].items() if not m)[:8])
    if ctx.control:
        # the control and the planted fault, each put in the program's
        # place: what they read against the same reference
        for label, kw in (("control_int8", {"lowp": "int8"}),
                          ("control_fp8", {"lowp": "fp8"}),
                          ("fault_half_batch", {"half_batch": True})):
            other = follow(ctx, model, job, hp, **kw)
            ctx.note(label, {n: v for n, (v, _) in
                             compare(other, ref).items()})
    return [harness.Check(name, v, ctx.limits[name])
            for name, (v, _) in got.items() if name in ctx.limits]
