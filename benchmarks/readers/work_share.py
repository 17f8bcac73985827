"""Required work over a peak and a time, with the work looked up by
name in the work module the metric names (``benchmarks/<module>.py``
with a ``WORK`` table: a configuration family brings its own, beside
the closed tables of ``work.py`` and ``scope_work.py``).  args:
``module``, ``work``, ``peak`` (a column of ``peaks.json``), and what
the time is:

- neither ``program`` nor ``match``: the whole traced window on every
  chip (a share of the whole step's peak);
- ``program``: the summed device time of that compiled program's runs;
- ``program`` and ``match``: the device time of the operations under
  that scope or kernel name in those runs.

Returns None where the work function finds nothing to count, or the
trace holds no such program or scope."""

import importlib

from benchmarks import program_trace


def read(view):
    trace = view["trace"]
    if trace is None:
        return None
    args = view["args"]
    table = importlib.import_module("benchmarks." + args["module"]).WORK
    peak = view["peaks"][args["peak"]]
    if not args.get("program"):
        work = table[args["work"]](view)
        if not work or not trace["window_s"]:
            return None
        return 100.0 * work / trace["window_s"] / (peak * trace["chips"])
    program = trace["programs"].get(args["program"])
    if not program or not program["total_s"]:
        return None
    seconds = program["total_s"]
    if args.get("match"):
        extracted = program_trace.current(view)
        if extracted is None:
            return None
        totals, runs = program_trace.program_scopes(extracted,
                                                    args["program"])
        seconds = program_trace.matching(totals, args["match"])
        if not runs or not seconds:
            return None
    work = table[args["work"]](view, program)
    if not work:
        return None
    return 100.0 * work / peak / seconds
