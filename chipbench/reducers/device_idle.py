"""Share of the traced window in which no operation ran on the device:
100 * (1 - union of the device operations' intervals / window), the mean
over the chips used."""


def reduce(args, facts, trace):
    if trace is None or not trace.ops or not trace.window_s:
        return None
    return 100.0 * max(0.0, 1.0 - trace.busy_s() / trace.window_s)
