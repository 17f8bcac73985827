"""Mean duration of one of the driver's own host spans in the traced
window, in milliseconds.  args: ``span``."""


def read(view):
    trace = view["trace"]
    if trace is None:
        return None
    span = trace["spans"].get(view["args"]["span"])
    if not span or not span["count"]:
        return None
    return span["total_s"] / span["count"] * 1e3
