"""Device milliseconds per unit of work in the traced window.

With ``module``: the mean device time of the executed programs whose name
matches it (one event per execution on the trace's ``XLA Modules`` line).
Otherwise: the device's busy time over the sum of the ``per`` terms (see
``_facts``), e.g. the dispatches the program counted in the same window."""
from chipbench.reducers import _facts


def reduce(args, facts, trace):
    if trace is None or not trace.ops:
        return None
    if "module" in args:
        secs = trace.seconds_matching(args["module"], line="modules")
        n = trace.count_matching(args["module"], line="modules")
        return 1e3 * secs / n if secs and n else None
    f = _facts.scoped(facts, "traced")
    per = _facts.total(f, args["per"]) if f else None
    busy = trace.busy_s()
    return 1e3 * busy / per if per and busy else None
