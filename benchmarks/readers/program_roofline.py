"""Required work of one compiled program's runs over the peak and over
the device time those runs took.  args: ``program`` (the module's name
in the trace, e.g. ``jit_decode``), ``work``, ``peak``."""

from benchmarks.work import WORK


def read(view):
    trace = view["trace"]
    if trace is None:
        return None
    program = trace["programs"].get(view["args"]["program"])
    if not program or not program["total_s"]:
        return None
    work = WORK[view["args"]["work"]](view, program)
    if not work:
        return None
    peak = view["peaks"][view["args"]["peak"]]
    return 100.0 * work / peak / program["total_s"]
