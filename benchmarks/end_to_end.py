"""The end-to-end metrics, each from the driver's observations of one
``--trace 0`` window.  All by the host's clock, over the whole window.
A function returns None where the window gives it nothing to read.
(``ttft_p95_ms`` is kept for its arithmetic and for ``calibrate.py``'s
sweep; no cell reports it yet: PERF.md section 2 and Open questions.)"""


def percentile(values, q):
    """The q-th percentile by the nearest-rank rule (no interpolation:
    a tail is a request that happened)."""
    if not values:
        return None
    ordered = sorted(values)
    rank = max(int(-(-q * len(ordered) // 100)), 1)
    return ordered[rank - 1]


def train_images_per_s(obs):
    return obs["train_images"] / obs["window_s"]


def out_tokens_per_s(obs):
    return obs["tokens_in_window"] / obs["window_s"]


def ttft_p95_ms(obs):
    """Over all requests DUE in the window, first token time minus the
    time the request was due.  A request that never got a first token
    has waited until the driver gave up (a minute past the close), and
    counts with that wait."""
    value = percentile(obs["ttft_s"], 95)
    return None if value is None else value * 1e3


def gap_p95_ms(obs):
    value = percentile(obs["gaps_s"], 95)
    return None if value is None else value * 1e3


METRICS = {
    "train_images_per_s": train_images_per_s,
    "out_tokens_per_s": out_tokens_per_s,
    "gap_p95_ms": gap_p95_ms,
}
