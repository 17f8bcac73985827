"""Device time a run of one compiled program spends under the program's
own scope or kernel names, in milliseconds: the operations whose scope
matches, summed over the program's runs that lie in the traced window
whole, over the count of those runs.  args: ``match`` (a regular
expression searched in the scope, or ``unscoped``), ``exclude``
(optional), ``program`` (the module's name in the trace; left out, the
program that spends most time under ``match``)."""

from benchmarks import program_trace


def read(view):
    extracted = program_trace.current(view)
    if extracted is None:
        return None
    args = view["args"]
    programs = [args["program"]] if args.get("program") \
        else program_trace.programs_carrying(extracted, args["match"])
    best = None
    for program in programs:
        totals, runs = program_trace.program_scopes(extracted, program)
        seconds = program_trace.matching(totals, args["match"],
                                         args.get("exclude"))
        if runs and seconds is not None and (
                best is None or seconds > best[0]):
            best = (seconds, runs)
    if best is None:
        return None
    return best[0] / best[1] * 1e3
