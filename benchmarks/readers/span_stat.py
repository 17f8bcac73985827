"""Mean of one keyword argument of the program's own spans in the
traced window.  args: ``span``, ``stat``, ``scale`` (optional factor,
e.g. 0.001 from microseconds to milliseconds)."""

from benchmarks import program_trace


def read(view):
    extracted = program_trace.current(view)
    if extracted is None:
        return None
    args = view["args"]
    values = [span[4][args["stat"]]
              for span in program_trace.spans_in_window(
                  extracted, args["span"])
              if isinstance(span[4].get(args["stat"]), (int, float))]
    if not values:
        return None
    return sum(values) / len(values) * args.get("scale", 1.0)
