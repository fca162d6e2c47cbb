"""A kernel's share of its roofline in the traced window: the least time
the chip could take for the work the algorithm needed (``costs.<cost>`` of
the traced window's facts, the larger of operations over the bf16 peak and
bytes over the HBM peak) over the device time of the kernel's events.
The kernel's events are those whose name or source text matches
``pattern``. No event: nothing returned, never 0."""
from chipbench import costs
from chipbench.reducers import _facts


def _work(cost, f):
    s = f["sizes"]
    if cost == "paged_decode_tokens":
        return costs.paged_decode_tokens(s, f.get("decode_contexts", []))
    if cost == "flash_fwd_call":
        flops, nbytes = costs.flash_fwd_call(s, f["batch"], f["sequence"])
        calls = s["L"] * f["steps"]
        return flops * calls, nbytes * calls
    raise SystemExit(f"chipbench: no cost function {cost!r}")


def reduce(args, facts, trace):
    f = _facts.scoped(facts, "traced")
    if trace is None or f is None:
        return None
    secs = trace.seconds_matching(args["pattern"])
    if not secs:
        return None
    flops, nbytes = _work(args["cost"], f)
    least, _ = costs.least_seconds(flops, nbytes, f["peaks"])
    return 100.0 * least / secs
