"""The one general traffic generator: a mix is a data file of
parameters under ``benchmark/traffic/``, and this reads it.

Request mixes (``"generator": "requests"``): prompt and output lengths
are log-normal, clipped. So that every seed does the same work, the
mix is a fixed multiset: ``pool`` lengths taken at evenly spaced
quantiles of each distribution, paired with each other by a fixed
shuffle (``pairing_seed`` of the file); ``--seed`` only orders the
pool and draws the token ids, and a closed loop deals the pool round
the clients. An open loop has no pool: its lead-in and its window each
get ``rate_per_s`` x their length of arrivals, whose gaps are evenly
spaced quantiles of the exponential distribution scaled to fill the
stretch, and as many lengths; ``--seed`` orders both. So every seed
puts the same requests and the same gaps inside the window.
"""

import math
import statistics

import numpy


def lognormal_lengths(spec, count):
    """``count`` lengths at evenly spaced quantiles of
    lognormal(median, sigma), clipped to [min, max]."""
    normal = statistics.NormalDist()
    out = []
    for i in range(count):
        z = normal.inv_cdf((i + 0.5) / count)
        value = round(spec["median"] * math.exp(spec["sigma"] * z))
        out.append(int(min(spec["max"], max(spec["min"], value))))
    return numpy.asarray(out, numpy.int64)


def exponential_gaps(rate_per_s, count):
    q = (numpy.arange(count) + 0.5) / count
    return -numpy.log1p(-q) / rate_per_s


def paired_lengths(mix, count):
    """``count`` (prompt, output) lengths: each distribution's evenly
    spaced quantiles, paired by the file's fixed shuffle."""
    prompts = lognormal_lengths(mix["prompt_len"], count)
    outputs = lognormal_lengths(mix["output_len"], count)
    pairing = numpy.random.Generator(
        numpy.random.PCG64(int(mix["pairing_seed"])))
    return prompts, outputs[pairing.permutation(count)]


def request_plan(mix, seed, vocab, seconds):
    """What the load generator sends: ``{"loop", "clients",
    "send_for_s", "lead_in_s", "requests": [{"tokens", "n_tokens",
    "client" or "due_s"}]}``. Lengths and gaps are the mix's fixed
    multisets in an order drawn from ``seed``; token ids are uniform
    from ``seed``."""
    rng = numpy.random.Generator(numpy.random.PCG64(int(seed)))
    lead_in = float(mix["lead_in_s"])
    plan = {"loop": mix["loop"], "lead_in_s": lead_in,
            "send_for_s": lead_in + float(seconds), "requests": []}

    def add(prompts, outputs, **extra):
        for n, (prompt, output) in enumerate(zip(prompts, outputs)):
            plan["requests"].append(dict(
                {key: value[n] for key, value in extra.items()},
                tokens=rng.integers(0, vocab, int(prompt)).tolist(),
                n_tokens=int(output)))

    if mix["loop"] == "closed":
        pool = int(mix["pool"])
        prompts, outputs = paired_lengths(mix, pool)
        order = rng.permutation(pool)
        plan["clients"] = int(mix["clients"])
        add(prompts[order], outputs[order],
            client=[n % plan["clients"] for n in range(pool)])
        return plan
    # open loop: the lead-in and the window each get their own fixed
    # multiset of arrivals and lengths, rate x its length of them, so
    # every seed puts the same work inside the window
    start = 0.0
    for length in (lead_in, float(seconds)):
        count = int(round(mix["rate_per_s"] * length))
        prompts, outputs = paired_lengths(mix, count)
        # one gap more than arrivals: the last one ends the stretch,
        # so no arrival sits on the boundary
        gaps = exponential_gaps(mix["rate_per_s"], count + 1)
        gaps = (gaps * (length / gaps.sum()))[rng.permutation(count + 1)]
        order = rng.permutation(count)
        add(prompts[order], outputs[order],
            due_s=(start + numpy.cumsum(gaps[:count])).tolist())
        start += length
    return plan
