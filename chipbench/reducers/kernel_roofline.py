"""A kernel's share of its roofline in the traced window: the least time
the chip could take for the work the algorithm needed (the function that
``cost`` names in the ``costs`` of the family the facts name, given the
traced window's facts: the larger of operations over the bf16 peak and
bytes over the HBM peak) over the device time of the kernel's events.
The kernel's events are those whose name or source text matches
``pattern``. No event: nothing returned, never 0."""
from chipbench import harness
from chipbench.reducers import _facts


def reduce(args, facts, trace):
    f = _facts.scoped(facts, "traced")
    if trace is None or f is None:
        return None
    secs = trace.seconds_matching(args["pattern"])
    if not secs:
        return None
    work = getattr(harness.family(f, "costs"), args["cost"], None)
    if work is None:
        raise SystemExit(f"chipbench: no cost function {args['cost']!r} "
                         f"in families/{f['family']}/costs.py")
    flops, nbytes = work(f)
    least, _ = harness.least_seconds(flops, nbytes, f["peaks"])
    return 100.0 * least / secs
