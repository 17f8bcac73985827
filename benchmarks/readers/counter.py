"""A number the driver read from the program's counters.  args:
``path``, dotted, into the driver's observations."""


def read(view):
    value = view["obs"]
    for key in view["args"]["path"].split("."):
        if not isinstance(value, dict) or key not in value:
            return None
        value = value[key]
    return value
