"""The device's idle time in the traced window that the program's own
spans cover: the window less the union of the device's operations
(as ``program_trace.idle_gaps`` takes it), intersected with the union of
the spans named in ``spans`` (``null``: the whole window) less the
union of those named in ``minus``.  An exact intersection, not a label
a gap: classes whose spans do not overlap split the idle time with
nothing counted twice.  args: ``spans``, ``minus`` (optional), ``per``:
a span's name, to give milliseconds a span of it that lies in the
window whole, or ``null``, to give a percentage of the window.

None when untraced, where the window holds none of the program's own
spans (a program that has no such names), or where it holds no ``per``
span."""

from benchmarks import program_trace, trace_reduce


def _union(extracted, names, lo, hi):
    """Sorted, disjoint ``[start, end]`` of the named spans, clipped to
    ``[lo, hi)``."""
    return trace_reduce.merge(
        (max(span[1], lo), min(span[1] + span[2], hi))
        for span in extracted["spans"] if span[0] in names
        and span[1] + span[2] > lo and span[1] < hi)


def _intersect(first, second):
    """Intersection of two sorted, disjoint interval lists."""
    out, i, j = [], 0, 0
    while i < len(first) and j < len(second):
        start = max(first[i][0], second[j][0])
        end = min(first[i][1], second[j][1])
        if start < end:
            out.append((start, end))
        if first[i][1] < second[j][1]:
            i += 1
        else:
            j += 1
    return out


def read(view):
    extracted = program_trace.current(view)
    if extracted is None or not program_trace.spans_in_window(extracted):
        return None
    lo, hi = program_trace.window_of(extracted)
    busy = trace_reduce.merge(
        (max(start, lo), min(start + duration, hi))
        for _p, _s, start, duration in extracted["ops"]
        if start + duration > lo and start < hi)
    idle = trace_reduce.idle_between(busy, lo, hi)
    args = view["args"]
    cover = [(lo, hi)] if args["spans"] is None \
        else _union(extracted, args["spans"], lo, hi)
    if args.get("minus"):
        cover = _intersect(cover, trace_reduce.idle_between(
            _union(extracted, args["minus"], lo, hi), lo, hi))
    ns = sum(end - start for start, end in _intersect(idle, cover))
    if args["per"] is None:
        return 100.0 * ns / (hi - lo)
    count = len(program_trace.spans_in_window(extracted, args["per"]))
    return ns / count / 1e6 if count else None
