"""Required work of a ``cohere2_moe`` configuration's traced window:
matrix FLOPs and bytes from the configuration's shapes, the driver's
token events, the engine's counters (``serve_window_moe`` puts them
under ``obs["traced"]["hybrid"]``) and the ``start`` and ``len`` of the
engine's span around every chunk, read from the trace.
What the published mathematics requires, whatever implements it: a
query counts the positions it SEES (the live prefix, a window at most in
a sliding layer), the experts count by the token-expert pairs routed to
the held experts and by the held experts that some token chose, not by
what a dense pass would touch; only matrix work is counted in FLOPs
(rotations, norms, activations and the softmax are left out), so a share
can only read too low.  Nothing here imports the program.  A function
returns None where the observations hold nothing to count (a program
without the counters).
"""

from benchmarks import program_trace
from benchmarks.work import _tokens_in

CHUNK_SPAN = "veles:gen/prefill_chunk"


def dims(config):
    kinds = config["layer_types"][:config["num_hidden_layers"]]
    return {
        "d": config["hidden_size"], "vocab": config["vocab_size"],
        "n_window": kinds.count("sliding_attention"),
        "n_full": kinds.count("full_attention"),
        "window": config["sliding_window"],
        "q": config["num_attention_heads"] * config["head_dim"],
        "kv": config["num_key_value_heads"] * config["head_dim"],
        "router": config["router_width"],
        "width": config["intermediate_size"],
        "shared": config["num_shared_experts"],
        "itemsize": 2 if config["dtype"] in ("bfloat16", "float16")
        else 4,
    }


def expert_params(m):
    """One gated expert: two projections in, one back."""
    return 3 * m["d"] * m["width"]


def attn_params(m):
    """One layer's four projections."""
    return 2 * m["d"] * (m["q"] + m["kv"])


def layer_dense_params(m):
    """One layer outside its routed experts and its router: attention
    and the shared experts."""
    return attn_params(m) + m["shared"] * expert_params(m)


def seen(m, position):
    """``(positions a query at ``position`` sees in a sliding layer, in
    a full layer)``, itself counted."""
    return min(position + 1, m["window"]), position + 1


def token_flops(m):
    """One token through every dense product of the stack: the routed
    experts, the attention over the cache and the head apart."""
    layers = m["n_window"] + m["n_full"]
    return layers * 2 * (layer_dense_params(m) + m["d"] * m["router"])


def attended_flops(m, first, count):
    """The attention of ``count`` queries at positions ``first ..``:
    scores and mixture over what each sees."""
    total = 0
    for position in range(first, first + count):
        in_window, in_full = seen(m, position)
        total += 4 * m["q"] * (m["n_window"] * in_window
                               + m["n_full"] * in_full)
    return total


def _counted(view, kinds):
    """The counters of the traced stretch, summed over ``kinds``."""
    traced = view["obs"].get("traced") or {}
    counted = traced.get("hybrid")
    if not counted or "host" not in counted:
        return None
    out = {"decode_calls": counted["decode_calls"]}
    out.update(counted["host"])
    for name in counted["decode"]:
        out[name] = sum(counted[kind][name] for kind in kinds)
    return out


def _chunks_in(view):
    """``(start, length)`` of the chunks the traced window fed: what
    the engine's span around each says."""
    extracted = program_trace.current(view)
    if extracted is None:
        return []
    return [(int(span[4]["start"]), int(span[4]["len"]))
            for span in program_trace.spans_in_window(extracted,
                                                      CHUNK_SPAN)
            if "start" in span[4] and "len" in span[4]]


def _chunk_flops(view, m):
    """The chunks' dense products and attention, and the head once a
    prompt finished (the one position a prompt needs)."""
    head = 2 * m["d"] * m["vocab"]
    total = sum(length * token_flops(m)
                + attended_flops(m, start, length)
                for start, length in _chunks_in(view))
    return total + head * sum(1 for _n, j in _tokens_in(view) if j == 0)


def wmoe_serve_flops(view, program=None):
    """Every chunk fed and every token decoded in the window, and the
    routed experts by the pairs the held experts computed."""
    counted = _counted(view, ("prefill", "decode"))
    if counted is None:
        return None
    m = dims(view["config"])
    total = counted["moe_local_pairs"] * 2 * expert_params(m) \
        + _chunk_flops(view, m)
    head = 2 * m["d"] * m["vocab"]
    for n, j in _tokens_in(view):
        if j:       # its query at position n + j - 1
            total += token_flops(m) + attended_flops(m, n + j - 1, 1) \
                + head
    return total


def wmoe_chunk_flops(view, program=None):
    """The chunk program's runs: as above without the decoded tokens,
    scaled to the runs the trace itself counted."""
    counted = _counted(view, ("prefill",))
    chunks = len(_chunks_in(view))
    if counted is None or not chunks:
        return None
    m = dims(view["config"])
    total = counted["moe_local_pairs"] * 2 * expert_params(m) \
        + _chunk_flops(view, m)
    runs = program["count"] if program is not None else chunks
    return total * runs / chunks


def attn_chunk_flops(view, program=None):
    """The chunks' attention alone (the kernel's required work): the
    scores and the mixture over what each real query sees, scaled to
    the runs the trace counted.  Rows a chunk visits and no query sees
    (a ring not yet full, the padding of a prompt's last chunk) are not
    required work."""
    chunks = _chunks_in(view)
    if _counted(view, ("prefill",)) is None or not chunks:
        return None
    m = dims(view["config"])
    total = sum(attended_flops(m, start, length)
                for start, length in chunks)
    runs = program["count"] if program is not None else len(chunks)
    return total * runs / len(chunks)


def _kv_row_bytes(m):
    return 2 * m["kv"] * m["itemsize"]


def _scaled(view, total, program):
    steps = _counted(view, ("decode",))["decode_calls"]
    if not steps:
        return None
    runs = program["count"] if program is not None else steps
    return total * runs / steps


def attn_window_decode_bytes(view, program=None):
    """The sliding layers of the decode steps: their projections once a
    step, and the keys and values each live slot's query sees (a window
    at most)."""
    counted = _counted(view, ("decode",))
    if counted is None:
        return None
    m = dims(view["config"])
    total = counted["decode_calls"] * m["n_window"] * attn_params(m) \
        * m["itemsize"] + counted["kv_rows_window"] * _kv_row_bytes(m)
    return _scaled(view, total, program)


def attn_full_decode_bytes(view, program=None):
    """The same of the full layers: every position of the live
    prefix."""
    counted = _counted(view, ("decode",))
    if counted is None:
        return None
    m = dims(view["config"])
    total = counted["decode_calls"] * m["n_full"] * attn_params(m) \
        * m["itemsize"] + counted["kv_rows_full"] * _kv_row_bytes(m)
    return _scaled(view, total, program)


def experts_decode_bytes(view, program=None):
    """The routed experts of the decode steps: each held expert that
    some live token chose."""
    counted = _counted(view, ("decode",))
    if counted is None:
        return None
    m = dims(view["config"])
    return _scaled(view, counted["moe_experts_touched"] * expert_params(m)
                   * m["itemsize"], program)


def wmoe_decode_bytes(view, program=None):
    """A decode step's required bytes: attention as above, the routed
    experts touched, the shared experts and the router (float32) of
    every layer, and the head's rows."""
    parts = [work(view, program) for work in (
        attn_window_decode_bytes, attn_full_decode_bytes,
        experts_decode_bytes)]
    if any(part is None for part in parts):
        return None
    m = dims(view["config"])
    layers = m["n_window"] + m["n_full"]
    fixed = layers * (m["shared"] * expert_params(m) * m["itemsize"]
                      + m["d"] * m["router"] * 4) \
        + m["d"] * m["vocab"] * m["itemsize"]
    steps = _counted(view, ("decode",))["decode_calls"]
    return sum(parts) + _scaled(view, steps * fixed, program)


WORK = {f.__name__: f for f in (
    wmoe_serve_flops, wmoe_chunk_flops, attn_chunk_flops,
    wmoe_decode_bytes,
    attn_window_decode_bytes, attn_full_decode_bytes,
    experts_decode_bytes)}
