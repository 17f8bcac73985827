"""Required work of a traced window, from the driver's observations and
``flops.py``: what the per-layer readers divide by a peak and a time.
Each function takes the reader's ``view`` and, where the work belongs to
runs of one compiled program, that program's statistics from the trace.
"""

from benchmarks import flops


def _tokens_in(view):
    traced = view["obs"]["traced"]
    lo, hi = traced["lo"], traced["hi"]
    return [(n, j) for t, n, j in view["obs"]["token_events"]
            if lo <= t < hi]


def convnet_train_flops(view, program=None):
    """Forward + backward of the images whose steps the window ran: by
    the trace's own count of step-program runs where one is given, else
    by the driver's count of images."""
    per_image = flops.convnet_train_flops_per_image(view["config"])
    if program is not None:
        return per_image * view["config"]["batch"] * program["count"]
    return per_image * view["obs"]["traced"]["train_images"]


def gpt_serve_flops(view, program=None):
    """Every prompt prefilled and every token decoded in the window."""
    config = view["config"]
    return sum(flops.gpt_prefill_flops(config, n) if j == 0
               else flops.gpt_decode_flops(config, n + j)
               for n, j in _tokens_in(view))


def gpt_prefill_flops(view, program=None):
    """The real (unpadded) prompt tokens prefilled in the window."""
    config = view["config"]
    return sum(flops.gpt_prefill_flops(config, n)
               for n, j in _tokens_in(view) if j == 0)


def gpt_decode_bytes(view, program=None):
    """Per decode step the weights once, plus the keys and values of the
    live lengths of the tokens decoded in the window."""
    config = view["config"]
    itemsize = 2 if config["dtype"] in ("bfloat16", "float16") else 4
    kv = sum(flops.gpt_kv_bytes(config, n + j, itemsize)
             for n, j in _tokens_in(view) if j)
    steps = program["count"] if program is not None \
        else view["obs"]["counters"]["decode_steps"]
    return steps * flops.gpt_weight_bytes(config, itemsize) + kv


WORK = {f.__name__: f for f in (
    convnet_train_flops, gpt_serve_flops, gpt_prefill_flops,
    gpt_decode_bytes)}
