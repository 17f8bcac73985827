"""The one general traffic generator: a data file of parameters in, a
list of requests out.  Nothing here knows a cell by name.

This is NOT a random draw from the stated distributions.  Every seed
gets the SAME set of prompt lengths, output lengths and inter-arrival
gaps, namely the n quantiles of the stated distributions (n = rate x
window), so the amount of work in a run does not depend on the seed;
the seed draws the ORDER of each of the three (so which long prompt
meets which burst differs from seed to seed) and every token id.
"""

import math

import numpy

_SQRT2 = math.sqrt(2.0)


def _norm_ppf(p):
    """Inverse normal CDF by bisection on erf (no scipy here)."""
    lo, hi = -10.0, 10.0
    for _ in range(80):
        mid = 0.5 * (lo + hi)
        if 0.5 * (1.0 + math.erf(mid / _SQRT2)) < p:
            lo = mid
        else:
            hi = mid
    return 0.5 * (lo + hi)


def _quantiles(n):
    return [(i + 0.5) / n for i in range(n)]


def lengths(spec, n):
    """``n`` lengths at the quantiles of ``spec``: ``{"dist":
    "lognormal", "median", "sigma", "min", "max"}`` or ``{"dist":
    "uniform", "min", "max"}`` or ``{"dist": "fixed", "value"}``."""
    dist = spec["dist"]
    if dist == "fixed":
        return numpy.full(n, int(spec["value"]), numpy.int64)
    if dist == "uniform":
        raw = [spec["min"] + q * (spec["max"] - spec["min"])
               for q in _quantiles(n)]
    elif dist == "lognormal":
        mu = math.log(spec["median"])
        raw = [math.exp(mu + spec["sigma"] * _norm_ppf(q))
               for q in _quantiles(n)]
    else:
        raise ValueError("unknown length distribution %r" % dist)
    return numpy.clip(numpy.rint(raw), spec["min"],
                      spec["max"]).astype(numpy.int64)


def gaps(spec, n):
    """``n`` inter-arrival gaps (seconds) at the quantiles of the
    arrival process, rescaled so that their mean is exactly
    ``1 / rate_rps``: ``poisson`` (exponential gaps) or ``gamma`` with
    a coefficient of variation ``cv`` (shape ``1 / cv**2``)."""
    rate = float(spec["rate_rps"])
    process = spec.get("process", "poisson")
    if process == "poisson":
        raw = numpy.array([-math.log(1.0 - q) for q in _quantiles(n)])
    elif process == "gamma":
        shape = 1.0 / float(spec["cv"]) ** 2
        # quantiles of a gamma by sorting a large fixed-seed sample:
        # the same for every run, which is all that is needed
        sample = numpy.sort(numpy.random.default_rng(0).gamma(
            shape, 1.0, 64 * n))
        raw = sample[32::64][:n]
    elif process == "uniform":
        raw = numpy.ones(n)
    else:
        raise ValueError("unknown arrival process %r" % process)
    return raw * (n / rate / raw.sum())


def generate(spec, vocab, seed, lead_in_s, window_s):
    """The requests of one run: dicts ``{"due", "tokens",
    "max_new_tokens"}`` with ``due`` in seconds from the start of the
    lead-in; the window is ``[lead_in_s, lead_in_s + window_s)``.

    The window's requests are ONE cycle: the quantiles of the stated
    distributions, each of the three lists in an order drawn from the
    seed, their gaps summing to the window's length.  The lead-in
    replays the END of that same cycle, so the window opens on the state
    it will close on.

    ``spec`` keys: ``arrivals`` (``process`` names the distribution
    whose quantiles the gaps are: ``poisson`` = exponential gaps),
    ``prompt_len``, ``output_len``, optionally ``shared_prefix``
    (``{"groups": how many seeded prefixes, "share": the part of each
    prompt taken from its group's prefix}``)."""
    rate = float(spec["arrivals"]["rate_rps"])
    n = max(int(round(rate * window_s)), 1)
    rng = numpy.random.default_rng([int(seed), 7])
    prompt = lengths(spec["prompt_len"], n)[rng.permutation(n)]
    output = lengths(spec["output_len"], n)[rng.permutation(n)]
    gap = gaps(spec["arrivals"], n)[rng.permutation(n)]
    gap = gap * (window_s / gap.sum())
    at = numpy.cumsum(gap) - 0.5 * gap      # place in the cycle
    shared = spec.get("shared_prefix")
    prefixes = None
    if shared:
        prefixes = [rng.integers(0, vocab, int(spec["prompt_len"]["max"]))
                    for _ in range(int(shared["groups"]))]

    def request(i, due):
        tokens = rng.integers(0, vocab, int(prompt[i]))
        if prefixes is not None:
            keep = int(float(shared["share"]) * len(tokens))
            tokens[:keep] = prefixes[i % len(prefixes)][:keep]
        return {"due": float(due), "tokens": tokens.astype(numpy.int32),
                "max_new_tokens": int(output[i])}

    requests = []
    laps = int(math.ceil(lead_in_s / window_s)) if lead_in_s > 0 else 0
    for lap in range(laps, 0, -1):
        for i in range(n):
            due = lead_in_s + at[i] - lap * window_s
            if due >= 0:
                requests.append(request(i, due))
    requests += [request(i, lead_in_s + at[i]) for i in range(n)]
    return requests
