"""Device duration of one compiled program's runs, in milliseconds.
args: ``program``, ``stat`` (``median`` or ``mean``)."""


def read(view):
    trace = view["trace"]
    if trace is None:
        return None
    program = trace["programs"].get(view["args"]["program"])
    if not program:
        return None
    return program[view["args"]["stat"] + "_s"] * 1e3
