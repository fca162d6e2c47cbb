"""The ``q``-th percentile of a list of readings the generator kept
(``facts[args["of"]]``), e.g. every request's time to first token."""
from chipbench import harness


def reduce(args, facts, trace):
    values = facts.get(args["of"])
    if not values:
        return None
    return harness.percentile(values, float(args["q"]))
