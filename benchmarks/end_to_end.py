"""The end-to-end metrics, each from the driver's observations of one
``--trace 0`` window.  All by the host's clock, over the whole window.
A function returns None where the window gives it nothing to read.
(``ttft_p95_ms`` is kept for its arithmetic and for ``calibrate.py``'s
sweep; no cell reports it yet: PERF.md section 2 and Open questions.)"""


def ceil_pct(n, pct):
    """``pct`` percent of ``n``, rounded up in integer arithmetic, and
    at least 1: the rank of a nearest-rank percentile, and the number of
    values in a tail."""
    return max(int(-(-pct * n // 100)), 1)


def percentile(values, q):
    """The q-th percentile by the nearest-rank rule (no interpolation:
    a tail is a request that happened)."""
    if not values:
        return None
    ordered = sorted(values)
    return ordered[ceil_pct(len(ordered), q) - 1]


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


def gap_p97_ms(obs):
    """The 97th percentile of all gaps between consecutive output tokens
    whose later token fell in the window: in the expert cells, a decode
    step that waited behind ONE prefill or chunk.

    There the gaps are two populations, a plain decode step and a step
    behind a prefill or a chunk (``command_a_plus_05_2026.mixed_len``:
    medians 11-12 and 60-61 ms), and the stalled share of a window's
    gaps was 4.4 to 7.3% over 32 windows on a TPU v5e: the 95th
    percentile fell on the edge between them and read either one by the
    window; the 97th lies inside the stalled population in each.  A
    mean of the slowest 2% read that population's own top too: gaps
    behind two or more prefills stacked in one scheduler step, 0.2 to
    1.1% of all gaps, as many as the seed's order of arrivals makes meet,
    so its sets of windows spread 10-79% where this percentile's
    spread 0.6-3.5%."""
    value = percentile(obs["gaps_s"], 97)
    return None if value is None else value * 1e3


METRICS = {
    "train_images_per_s": train_images_per_s,
    "out_tokens_per_s": out_tokens_per_s,
    "gap_p95_ms": gap_p95_ms,
    "gap_p97_ms": gap_p97_ms,
}
