"""Host time by the program's own spans (``veles:<cat>/<name>``), in
milliseconds.  args: ``span``; ``where`` (optional: stats the span must
carry, e.g. ``{"train": 1}``); ``minus`` (optional: names of spans whose
time inside each ``span``, on its thread, is taken out: the waits on the
device); ``rest_of_window_per`` (optional ``{"span", "where"}``: instead
of the mean, the traced window less all of ``span``'s time, over the
count of that other span: the host's share of a synchronous step)."""

from benchmarks import program_trace


def read(view):
    extracted = program_trace.current(view)
    if extracted is None:
        return None
    args = view["args"]
    spans = program_trace.spans_in_window(extracted, args["span"],
                                          args.get("where"))
    if not spans:
        return None
    total = sum(span[2] for span in spans)
    if args.get("minus"):
        inner = [s for s in extracted["spans"] if s[0] in args["minus"]]
        total -= sum(program_trace.nested_ns(spans, inner))
    per = args.get("rest_of_window_per")
    if not per:
        return total / len(spans) / 1e6
    steps = program_trace.spans_in_window(extracted, per["span"],
                                          per.get("where"))
    lo, hi = program_trace.window_of(extracted)
    if not steps:
        return None
    return (hi - lo - total) / len(steps) / 1e6
