"""The one general traffic generator.  A traffic mix is a data file; this
turns it, ``--seconds`` and ``--seed`` into the run's requests.

A design, not a sample: the count of requests, their lengths and their due
times follow from the file and ``--seconds`` alone and are the same for
every seed.  The seed decides the token ids and which length pair goes in
which arrival slot, and nothing else.

File keys used here: ``arrivals`` (``{"kind": "paced", "rate_rps"}``) and
``lengths`` (``prompt`` and ``output``: clipped log-normals given by
``median``, ``sigma``, ``min``, ``max``).
"""
import math
from statistics import NormalDist

from .weights import host_rng


def due_times(arrivals, seconds):
    """Seconds after the window opens at which each request is due: evenly
    spaced at the rate, the first at 0, all inside ``seconds``."""
    if arrivals["kind"] != "paced":
        raise ValueError(f"unknown arrivals kind {arrivals['kind']!r}")
    rate = float(arrivals["rate_rps"])
    n = int(math.ceil(seconds * rate - 1e-9))
    return [k / rate for k in range(n)]


def quantile_grid(dist, n):
    """``n`` lengths: the (i + 0.5)/n quantiles of a clipped log-normal."""
    nd = NormalDist()
    out = []
    for i in range(n):
        x = math.exp(math.log(dist["median"])
                     + dist["sigma"] * nd.inv_cdf((i + 0.5) / n))
        out.append(int(min(max(round(x), dist["min"]), dist["max"])))
    return out


def _stride_order(n):
    """A fixed permutation of range(n) that scatters neighbours: steps of
    the whole number nearest n / golden ratio that is coprime to n."""
    if n < 3:
        return list(range(n))
    step = max(1, round(n * 0.6180339887))
    while math.gcd(step, n) != 1:
        step += 1
    return [(i * step) % n for i in range(n)]


def design(traffic, seconds, seed, vocab_size):
    """The run's requests, in arrival order: ``[{"due_s", "prompt" (list of
    token ids), "max_new_tokens"}]``."""
    import numpy as np
    due = due_times(traffic["arrivals"], seconds)
    n = len(due)
    prompts = quantile_grid(traffic["lengths"]["prompt"], n)
    outputs = quantile_grid(traffic["lengths"]["output"], n)
    # pair each prompt length with an output length far from its own rank,
    # the same pairing for every seed: the multiset of pairs is fixed
    pair = _stride_order(n)
    pairs = [(prompts[i], outputs[pair[i]]) for i in range(n)]
    slots = [int(i) for i in host_rng(seed, stream=12).permutation(n)]
    rng = host_rng(seed, stream=13)
    out = []
    for k in range(n):
        p_len, o_len = pairs[slots[k]]
        out.append({
            "due_s": due[k],
            "prompt": rng.integers(0, vocab_size, p_len,
                                   dtype=np.int64).tolist(),
            "max_new_tokens": o_len})
    return out
