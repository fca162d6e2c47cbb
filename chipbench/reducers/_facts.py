"""How a reducer's arguments name the facts of a window.

A *term* is a list of keys whose values are multiplied; a leading ``"-"``
negates it. A key is looked up in the facts (``"max_slots"``), or, with the
prefix ``counters.``, among the program's counters as they rose over the
interval. ``scope`` picks the interval: ``"window"`` (the whole measured
window) or ``"traced"`` (the part of it the profiler covered)."""
from __future__ import annotations


def scoped(facts, scope):
    if scope == "traced":
        return facts.get("traced")
    return facts


def lookup(facts, key):
    if key.startswith("counters."):
        return facts.get("counters", {}).get(key[len("counters."):])
    return facts.get(key)


def total(facts, terms):
    """Sum of the terms, or ``None`` where a key has nothing to read."""
    out = 0.0
    for term in terms:
        sign, keys = (-1.0, term[1:]) if term[0] == "-" else (1.0, term)
        prod = sign
        for k in keys:
            v = lookup(facts, k)
            if v is None:
                return None
            prod *= v
        out += prod
    return out
