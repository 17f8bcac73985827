"""Required work of the operations under one scope or kernel name over
the peak and over the device time those operations took, in one
compiled program's runs.  args: ``program``, ``match`` (as
``scope_device_ms``), ``work`` (a name in ``scope_work.py``), ``peak``
(a column of ``peaks.json``)."""

from benchmarks import program_trace
from benchmarks.scope_work import WORK


def read(view):
    extracted = program_trace.current(view)
    if extracted is None:
        return None
    args = view["args"]
    totals, runs = program_trace.program_scopes(extracted,
                                                args["program"])
    seconds = program_trace.matching(totals, args["match"])
    if not runs or not seconds:
        return None
    work = WORK[args["work"]](view)
    if not work:
        return None
    return 100.0 * work / view["peaks"][args["peak"]] / seconds
