"""Mean device milliseconds of the executed programs whose name matches
``module`` (one event per execution on the trace's ``XLA Modules`` line)
in the traced window. None executed: nothing returned."""


def reduce(args, facts, trace):
    if trace is None or not trace.ops:
        return None
    secs = trace.seconds_matching(args["module"], line="modules")
    n = trace.count_matching(args["module"], line="modules")
    return 1e3 * secs / n if secs and n else None
