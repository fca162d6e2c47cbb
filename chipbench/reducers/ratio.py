"""``scale * sum(num terms) / sum(den terms)`` over the facts of a window
(see ``_facts`` for terms). Nothing to divide by: nothing returned."""
from chipbench.reducers import _facts


def reduce(args, facts, trace):
    f = _facts.scoped(facts, args.get("scope", "window"))
    if f is None:
        return None
    num, den = _facts.total(f, args["num"]), _facts.total(f, args["den"])
    if num is None or not den:
        return None
    return float(args.get("scale", 1.0)) * num / den
