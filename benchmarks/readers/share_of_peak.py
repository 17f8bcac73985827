"""Required work of the whole traced window over the chip's peak: the
whole step's share (``mfu``).  args: ``work`` (a name in ``work.py``),
``peak`` (a column of ``peaks.json``)."""

from benchmarks.work import WORK


def read(view):
    trace = view["trace"]
    if trace is None:
        return None
    work = WORK[view["args"]["work"]](view)
    if not work:
        return None
    peak = view["peaks"][view["args"]["peak"]] * trace["chips"]
    return 100.0 * work / trace["window_s"] / peak
