"""Traffic kind ``closed_loop``: N callers, each sends its next request
when its last one ends.

The mix file gives ``callers``, the two length distributions
(``prompt_tokens`` and ``new_tokens``: lognormal ``median``/``sigma``
clipped to ``min``/``max``), ``distinct_sizes`` (how many (prompt, new)
pairs the mix is made of) and ``check_requests``. Every seed serves the
same pairs (the quantiles of the two distributions, paired by a
permutation fixed in the mix file) in the same order (permutation after
permutation of them, drawn from ``pairing_seed``) with other token ids and
other weights: a window holds some forty requests, and with the order left
to the seed the 95th percentile of their first-token times, which is its
second or third longest, swung by a factor of 2.7 between seeds (673 to
1806 ms, my chip runs, PR 23). The seed changes what is computed, not how
much of it or when. Greedy, no eos, ids uniform over the vocabulary.

A caller takes its next request and submits it in one turn, so the engine
is sent the same sequence whichever callers race. When the window has
closed the load runs on until every request submitted inside it has its
first token: each counts its whole wait, none a wait cut off at the close
(that cut made the mean swing by 2.5 % with whether a forty-first request
fell inside, BENCHMARK_REFUSED of PR 23).
"""
from __future__ import annotations

import statistics
import threading
import time

import numpy as np

from chipbench import harness


# how long past the window's close the load runs on for the first tokens
# of the requests submitted inside the window
DRAIN_SECONDS = 60.0


# ---------------------------------------------------------------------------
# traffic
# ---------------------------------------------------------------------------
def _quantiles(dist, n):
    nd = statistics.NormalDist()
    out = []
    for i in range(n):
        z = nd.inv_cdf((i + 0.5) / n)
        v = dist["median"] * np.exp(dist["sigma"] * z)
        out.append(int(min(max(round(v), dist["min"]), dist["max"])))
    return out


def sizes_of(mix):
    """The (prompt, new) pairs of the mix: the same for every seed."""
    n = int(mix["distinct_sizes"])
    prompts = _quantiles(mix["prompt_tokens"], n)
    news = _quantiles(mix["new_tokens"], n)
    pairing = np.random.default_rng(int(mix["pairing_seed"])).permutation(n)
    return [(prompts[i], news[int(pairing[i])]) for i in range(n)]


class Requests:
    """The endless sequence of requests: permutation after permutation of
    the mix's pairs (the mix's order), ids drawn from the seed as each is
    taken."""

    def __init__(self, mix, vocab, seed):
        self._pairs = sizes_of(mix)
        self._order_rng = np.random.default_rng(
            [int(mix["pairing_seed"]), 7])
        self._rng = np.random.default_rng([int(seed), 1])
        self._vocab = vocab
        self._order = []
        self.turn = threading.Lock()

    def take(self):
        """The next request; the caller holds ``turn``."""
        if not self._order:
            self._order = list(
                self._order_rng.permutation(len(self._pairs)))
        p, n = self._pairs[int(self._order.pop())]
        ids = self._rng.integers(0, self._vocab, p).astype(np.int32)
        return ids, n


class Record:
    __slots__ = ("prompt", "n_new", "submit", "stamps", "tokens", "error",
                 "reason", "caller", "ended")

    def __init__(self, caller, prompt, n_new):
        self.caller, self.prompt, self.n_new = caller, prompt, n_new
        self.submit = None
        self.stamps, self.tokens = [], []
        self.error = self.reason = self.ended = None

    @property
    def complete(self):
        return self.error is None and len(self.tokens) == self.n_new


def _caller(idx, engine, requests, records, stop):
    """One caller: submit, iterate the stream stamping each token, again."""
    while not stop.is_set():
        # taken and submitted in one turn: whichever callers race for it,
        # the engine is sent the mix's requests in the mix's order
        with requests.turn:
            prompt, n_new = requests.take()
            rec = Record(idx, prompt, n_new)
            rec.submit = time.perf_counter()
            records.append(rec)
            try:
                stream = engine.submit(prompt, max_new_tokens=n_new)
            except Exception as e:      # closed under us, or refused
                rec.error, rec.ended = repr(e), time.perf_counter()
                return
        try:
            for tok in stream:
                rec.stamps.append(time.perf_counter())
                rec.tokens.append(int(tok))
            rec.reason = stream.result(timeout=5).finish_reason
        except Exception as e:
            rec.error = repr(e)
        rec.ended = time.perf_counter()


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------
def run(ctx):
    from chipbench import program
    model, mix = ctx.config["model"], ctx.mix
    s = harness.family(ctx.config, "weights").sizes(model)
    fam_program = harness.family(ctx.config, "program")
    serve_args = dict(ctx.config["serve"])
    serve_args["max_new_tokens"] = int(mix["new_tokens"]["max"])

    net = fam_program.build_model(model, ctx.seed)
    ctx.part("weights_and_model")
    engine = program.build_engine(net, serve_args)
    ctx.part("engine_and_warmup")
    requests = Requests(mix, s["V"], ctx.seed)
    records, stop = [], threading.Event()
    callers = [threading.Thread(target=_caller, daemon=True,
                                args=(i, engine, requests, records, stop))
               for i in range(int(mix["callers"]))]
    for t in callers:
        t.start()
    # ramp: every caller has a request in flight and a first token back
    deadline = time.perf_counter() + 300
    while True:
        first = {r.caller for r in list(records) if r.stamps}
        if len(first) == len(callers):
            break
        if time.perf_counter() > deadline or \
                any(r.error for r in list(records)):
            stop.set()
            engine.close(timeout=0.0)
            raise SystemExit("chipbench: the ramp never got a first token "
                             "to every caller")
        time.sleep(0.01)

    ctx.part("ramp")
    trace_counters = fam_program.TRACE_COUNTERS
    warm_traces = program.traces(trace_counters)
    warm_compiles = ctx.compiles.n
    setup_s = ctx.mark_setup_done()
    t0 = time.perf_counter()
    c0 = program.counters(program.SERVE_COUNTERS)
    traced = None
    if ctx.trace:
        tw = harness.TracedWindow(ctx.keep_trace)
        ta = tw.start()
        ca = program.counters(program.SERVE_COUNTERS)
        time.sleep(min(float(mix["trace_seconds"]), ctx.seconds))
        cb = program.counters(program.SERVE_COUNTERS)
        tb = tw.stop()
        traced = {"t0": ta, "t1": tb,
                  "counters": {k: cb[k] - ca[k] for k in cb}, "tw": tw}
    remaining = t0 + ctx.seconds - time.perf_counter()
    if remaining > 0:
        time.sleep(remaining)
    t1 = time.perf_counter()
    c1 = program.counters(program.SERVE_COUNTERS)
    # the window is closed; the load runs on, unchanged, until every
    # request submitted inside it has its first token (a minute at the
    # most), so that each counts its whole wait under the window's load
    deadline = t1 + DRAIN_SECONDS
    while time.perf_counter() < deadline and any(
            not r.stamps and r.ended is None for r in list(records)
            if r.submit is not None and r.submit <= t1):
        time.sleep(0.005)
    drained = time.perf_counter()
    in_window = {"traces": program.traces(trace_counters) - warm_traces,
                 "backend_compiles": ctx.compiles.n - warm_compiles}
    stop.set()
    peak = harness.memory_peak_bytes(ctx.chips)
    engine.close(timeout=0.5)
    for t in callers:
        t.join(timeout=30)
    if any(t.is_alive() for t in callers):
        raise SystemExit("chipbench: a caller did not end")
    records = list(records)

    # -- the window's end-to-end metrics ---------------------------------
    win = t1 - t0
    n_tokens = sum(1 for r in records for x in r.stamps if t0 <= x <= t1)
    ttft, gaps, cut, late = [], [], [], 0
    for r in records:
        if r.submit is not None and t0 <= r.submit <= t1:
            # a request whose first token never came counts the wait up
            # to the end of the drain, and has failed
            ttft.append((r.stamps[0] if r.stamps else drained) - r.submit)
            late += not r.stamps and r.error is None
            cut.append(min(r.stamps[0] if r.stamps else t1, t1) - r.submit)
        if r.complete and t0 <= r.stamps[-1] <= t1:
            gaps.extend(b - a for a, b in zip(r.stamps, r.stamps[1:]))
    attempted = sum(1 for r in records
                    if r.submit is not None and r.submit <= t1)
    # a request that the window's end abandoned (engine.close) has not
    # failed: only what went wrong before the close counts
    bad = [r for r in records if r.ended is not None and r.ended <= drained
           and (r.error is not None or r.reason != "length")]
    failed = len(bad) + late
    if bad:
        ctx.note("failed_requests", [r.error or r.reason for r in bad][:5])
    e2e = {"serve_tokens_per_s": n_tokens / win,
           "ttft_mean_ms": 1e3 * sum(ttft) / len(ttft) if ttft else None,
           "itl_p95_ms": 1e3 * harness.percentile(gaps, 95)
           if gaps else None,
           "setup_s": setup_s}

    facts = _facts(s, ctx, records, t0, t1,
                   {k: c1[k] - c0[k] for k in c1}, serve_args)
    facts["ttft_ms"] = [1e3 * x for x in ttft]
    facts["itl_ms"] = [1e3 * x for x in gaps]
    ctx.note("window", {"requests_submitted": len(ttft),
                        "drain_s": drained - t1,
                        "no_first_token": late,
                        # the mean as the refused check read it: waits
                        # cut off at the close (BENCHMARK_REFUSED, PR 23)
                        "ttft_mean_cut_at_close_ms":
                            1e3 * sum(cut) / len(cut) if cut else None,
                        "ttft_longest_ms": sorted(
                            round(x) for x in facts["ttft_ms"])[-5:],
                        # the first 64 in the order submitted: which
                        # requests the window held, when two runs disagree
                        "ttft_by_submit_ms": [
                            round(x) for x in facts["ttft_ms"][:64]]})
    if traced is not None:
        facts["traced"] = _facts(s, ctx, records, traced["t0"],
                                 traced["t1"], traced["counters"],
                                 serve_args)

    # -- free the program, then the reference ----------------------------
    sample = _sample(records, int(mix["check_requests"]), ctx.seed)
    del engine, net
    program.release()
    trace = traced["tw"].reduce() if traced is not None else None
    checks = [harness.Check("compiles_in_window",
                            sum(in_window.values()), 0),
              harness.Check("failed_requests", failed, 0)]
    checks += check_served(ctx, model, sample)
    if ctx.control:
        for lowp in ("int8", "fp8"):
            got = check_served(ctx, model, sample, control=lowp)
            ctx.note("control_" + lowp, {c.name: c.value for c in got})
    return {"e2e": e2e, "facts": facts, "trace": trace,
            "attempted": attempted, "failed": failed, "checks": checks,
            "memory_peak_bytes": peak}


def _facts(s, ctx, records, t0, t1, counters, serve_args):
    """What the reducers read of the interval [t0, t1]: the counters'
    increase, the context of every decoded token stamped in it, the
    prompts whose first token fell in it."""
    contexts, prompts_done = [], []
    first_tokens = 0
    for r in records:
        p = len(r.prompt)
        for i, x in enumerate(r.stamps):
            if t0 <= x <= t1:
                if i == 0:
                    prompts_done.append(p)
                    first_tokens += 1
                else:
                    contexts.append(p + i)
    return {"seconds": t1 - t0, "family": ctx.config["family"], "sizes": s,
            "peaks": ctx.peaks, "chips": ctx.chips,
            "max_slots": int(serve_args["max_slots"]),
            "decode_contexts": contexts, "prompts_done": prompts_done,
            "tokens": len(contexts) + first_tokens, "counters": counters}


def _sample(records, n, seed):
    """The longest finished request and ``n - 1`` more, drawn from the
    seed among the finished ones."""
    done = [r for r in records if r.complete]
    if not done:
        return []
    done.sort(key=lambda r: r.submit)
    longest = max(done, key=lambda r: len(r.prompt) + r.n_new)
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng([int(seed), 2])
    picks = rng.permutation(len(rest))[:max(n - 1, 0)]
    return [longest] + [rest[int(i)] for i in picks]


def check_served(ctx, model, sample, control=None):
    """Run the reference once over each sampled prompt with its served
    tokens; the number compared is the widest gap by which a served token
    lies under the reference's best logit at its position. The request
    goes to the family's reference as it was served: how it is padded and
    in how many blocks the forward runs is the family's."""
    limits = ctx.limits
    reference = harness.family(ctx.config, "reference")
    if not sample:
        return [harness.Check("served_tokens_compared", 0,
                              limits["served_tokens_compared"],
                              at_least=True)]
    n_max = int(ctx.mix["new_tokens"]["max"])
    w = harness.family(ctx.config, "weights").make(model, ctx.seed)
    worst, hits, total = 0.0, 0, 0
    for r in sample:
        g, h = reference.served_gaps(model, w, r.prompt, r.tokens, n_max,
                                     control)
        worst = max(worst, float(g.max()))
        hits += int(h.sum())
        total += len(r.tokens)
    del w
    if control is None:
        ctx.note("greedy_agreement", hits / max(total, 1))
    return [harness.Check("served_logit_gap", worst,
                          limits["served_logit_gap"]),
            harness.Check("served_tokens_compared", total,
                          limits["served_tokens_compared"], at_least=True)]
