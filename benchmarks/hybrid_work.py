"""Required work of a ``nemotron_h`` configuration's traced window:
matrix FLOPs and bytes from the configuration's shapes, the driver's
token events and the expert layers' counters (``serve_hybrid`` puts
them under ``obs["traced"]["hybrid"]``).  What the published
mathematics requires, whatever implements it: the experts count by the
token-expert pairs routed to the held experts and by the held experts
that some token chose, not by what a dense pass would touch; only
matrix work is counted in FLOPs (the recurrence's elementwise update,
the convolution, norms and activations are left out), so a share can
only read too low.  Nothing here imports the program.  A function
returns None where the observations hold nothing to count (a program
without the counters).
"""

from benchmarks.work import _tokens_in


def dims(config):
    depth = config["num_hidden_layers"]
    pattern = config["hybrid_override_pattern"][:depth]
    d_inner = config["mamba_num_heads"] * config["mamba_head_dim"]
    state = config["n_groups"] * config["ssm_state_size"]
    return {
        "d": config["hidden_size"], "vocab": config["vocab_size"],
        "n_ssm": pattern.count("M"), "n_attn": pattern.count("*"),
        "n_moe": pattern.count("E"),
        "d_inner": d_inner, "conv_dim": d_inner + 2 * state,
        "ssm_heads": config["mamba_num_heads"],
        "ssm_head_dim": config["mamba_head_dim"],
        "ssm_state": config["ssm_state_size"],
        "conv_k": config["conv_kernel"],
        "q": config["num_attention_heads"] * config["head_dim"],
        "kv": config["num_key_value_heads"] * config["head_dim"],
        "router": config["router_width"],
        "latent": config["moe_latent_size"],
        "expert": config["moe_intermediate_size"],
        "shared": config["moe_shared_expert_intermediate_size"],
        "itemsize": 2 if config["dtype"] in ("bfloat16", "float16")
        else 4,
    }


def expert_params(m):
    """One routed expert: latent -> width -> latent."""
    return 2 * m["latent"] * m["expert"]


def ssm_params(m):
    """One Mamba-2 layer's matrices (in and out projections)."""
    return m["d"] * (m["d_inner"] + m["conv_dim"] + m["ssm_heads"]) \
        + m["d_inner"] * m["d"]


def attn_params(m):
    return 2 * m["d"] * (m["q"] + m["kv"])


def moe_dense_params(m):
    """One expert layer outside its routed experts: the latent's two
    projections and the shared expert (bf16) and the router (float32,
    counted apart where bytes are)."""
    return 2 * m["d"] * m["latent"] + 2 * m["d"] * m["shared"]


def token_flops(m, live):
    """One token through every dense product of the stack (the routed
    experts apart), its query against ``live`` positions, no head."""
    return (m["n_ssm"] * 2 * ssm_params(m)
            + m["n_attn"] * (2 * attn_params(m) + 4 * m["q"] * live)
            + m["n_moe"] * 2 * (moe_dense_params(m)
                                + m["d"] * m["router"]))


def _hybrid(view, kinds):
    """The counters of the traced stretch, summed over ``kinds``."""
    traced = view["obs"].get("traced") or {}
    counted = traced.get("hybrid")
    if not counted:
        return None
    out = {"decode_calls": counted["decode_calls"]}
    for name in counted["decode"]:
        out[name] = sum(counted[kind][name] for kind in kinds)
    return out


def hybrid_serve_flops(view, program=None):
    """Every prompt prefilled and every token decoded in the window:
    the dense products a token, causal attention, the head at the one
    position a prefill needs and at every decoded token, and the routed
    experts by the pairs the held experts computed."""
    counted = _hybrid(view, ("prefill", "decode"))
    if counted is None:
        return None
    m = dims(view["config"])
    head = 2 * m["d"] * m["vocab"]
    total = counted["moe_local_pairs"] * 2 * expert_params(m)
    for n, j in _tokens_in(view):
        if j == 0:      # a prompt of n tokens, each against its past
            total += n * token_flops(m, 0) + head \
                + m["n_attn"] * 4 * m["q"] * (n * (n + 1) // 2)
        else:
            total += token_flops(m, n + j) + head
    return total


def _live_slot_steps(view, steps):
    fill = view["obs"]["traced"].get("batch_fill_slots")
    return (fill or 0.0) * steps


def moe_decode_bytes(view, program=None):
    """The expert layers of the decode steps: router, latent
    projections and shared expert once a step, and each held expert
    that some live token chose."""
    counted = _hybrid(view, ("decode",))
    if counted is None:
        return None
    m = dims(view["config"])
    steps = counted["decode_calls"]
    fixed = m["n_moe"] * (moe_dense_params(m) * m["itemsize"]
                          + m["d"] * m["router"] * 4)
    return steps * fixed + counted["moe_experts_touched"] \
        * expert_params(m) * m["itemsize"]


def ssm_decode_bytes(view, program=None):
    """The state-space layers of the decode steps: the projections and
    the convolution once a step, and each LIVE slot's recurrent state
    (float32) and convolution tail read and written."""
    counted = _hybrid(view, ("decode",))
    if counted is None:
        return None
    m = dims(view["config"])
    steps = counted["decode_calls"]
    fixed = m["n_ssm"] * (ssm_params(m) + (m["conv_k"] + 1)
                          * m["conv_dim"]) * m["itemsize"]
    slot = m["n_ssm"] * (m["ssm_heads"] * m["ssm_head_dim"]
                         * m["ssm_state"] * 4
                         + (m["conv_k"] - 1) * m["conv_dim"]
                         * m["itemsize"])
    return steps * fixed + 2 * slot * _live_slot_steps(view, steps)


def hybrid_decode_bytes(view, program=None):
    """A decode step's required bytes: the expert and state-space
    layers as above, the attention layers' weights and the keys and
    values of the live lengths, the head's rows; scaled to the runs of
    the decode program the trace itself counted."""
    moe, ssm = moe_decode_bytes(view), ssm_decode_bytes(view)
    if moe is None or ssm is None:
        return None
    m = dims(view["config"])
    steps = _hybrid(view, ("decode",))["decode_calls"]
    if not steps:
        return None
    kv = sum(2 * m["n_attn"] * m["kv"] * (n + j) * m["itemsize"]
             for n, j in _tokens_in(view) if j)
    fixed = (m["n_attn"] * attn_params(m) + m["d"] * m["vocab"]) \
        * m["itemsize"]
    total = moe + ssm + kv + steps * fixed
    runs = program["count"] if program is not None else steps
    return total * runs / steps


WORK = {f.__name__: f for f in (
    hybrid_serve_flops, hybrid_decode_bytes, moe_decode_bytes,
    ssm_decode_bytes)}
