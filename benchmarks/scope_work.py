"""Required work of what runs under ONE scope or kernel name of the
program, beside ``work.py``'s counts for whole programs: what
``readers/scope_roofline.py`` divides by a peak and a time."""

from benchmarks import flops
from benchmarks.work import _tokens_in


def gpt_kv_read_bytes(view):
    """The keys and values of the live lengths of every token decoded
    in the window, and nothing else: what a decode attention kernel has
    to read (the weights belong to the other scopes)."""
    config = view["config"]
    itemsize = 2 if config["dtype"] in ("bfloat16", "float16") else 4
    return sum(flops.gpt_kv_bytes(config, n + j, itemsize)
               for n, j in _tokens_in(view) if j)


WORK = {f.__name__: f for f in (gpt_kv_read_bytes,)}
