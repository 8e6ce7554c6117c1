"""The general traffic generators. A traffic mix is a file of parameters
under ``perfbench/traffic/``; its ``kind`` picks one of these.

Steadiness rule (builder's contract): every seed gets the SAME multiset
of sizes and the SAME multiset of gaps between arrivals. Sizes and gaps
are the distribution's quantiles at the midpoints of n equal-probability
strata, not random draws. Their order comes from the mix's
``schedule_seed`` where it has one (then every run of the cell sees the
same bursts, and the run's seed makes only the prompts' tokens), else
from the run's seed (same work, another order).
"""

from __future__ import annotations

import math
from statistics import NormalDist
from typing import Any, Dict, List

import numpy as np


def lognormal_quantiles(n: int, median: float, sigma: float,
                        lo: int, hi: int) -> List[int]:
    """n stratified draws of a lognormal, clipped to [lo, hi]."""
    nd = NormalDist()
    out = []
    for i in range(n):
        z = nd.inv_cdf((i + 0.5) / n)
        out.append(int(min(hi, max(lo, round(median * math.exp(sigma * z))))))
    return out


def exponential_gaps(n: int, rate: float) -> List[float]:
    """n stratified inter-arrival gaps of a Poisson process of ``rate``
    per second, rescaled so that they sum to exactly n / rate."""
    gaps = [-math.log(1.0 - (i + 0.5) / n) / rate for i in range(n)]
    scale = (n / rate) / sum(gaps)
    return [g * scale for g in gaps]


def serve_requests(mix: Dict[str, Any], seed: int, seconds: float,
                   vocab: int) -> List[Dict[str, Any]]:
    """Open-loop requests for ``seconds`` of arrivals at the mix's fixed
    rate: ``[{"prompt": [...], "max_new_tokens": n, "arrival_s": t}]``.

    ``stop_fraction`` (default 1) ends the arrivals that early in the
    window, for a mix above capacity whose backlog must drain by about
    ``seconds``."""
    if mix.get("kind") != "serve_open_loop":
        raise ValueError(f"not a serve mix: kind={mix.get('kind')!r}")
    if mix.get("arrivals", "poisson") != "poisson":
        raise ValueError(f"arrivals {mix['arrivals']!r}; have poisson")
    rate = float(mix["rate_rps"])
    span = seconds * float(mix.get("stop_fraction", 1.0))
    n = max(1, int(round(rate * span)))
    rng = np.random.default_rng([int(seed) % 2 ** 32, 0x5e7e])
    order = (np.random.default_rng([int(mix["schedule_seed"]), 0x0de4])
             if "schedule_seed" in mix else rng)
    p, o = mix["prompt_len"], mix["output_len"]
    plens = lognormal_quantiles(n, p["median"], p["sigma"], p["min"],
                                p["max"])
    olens = lognormal_quantiles(n, o["median"], o["sigma"], o["min"],
                                o["max"])
    # Independent orders: prompt and output lengths are not correlated.
    plens = [plens[i] for i in order.permutation(n)]
    olens = [olens[i] for i in order.permutation(n)]
    gaps = exponential_gaps(n, rate)
    gaps = [gaps[i] for i in order.permutation(n)]
    t, out = 0.0, []
    for i in range(n):
        t += gaps[i]
        # The first request is due at the first gap, the last at `span`.
        out.append({
            "prompt": rng.integers(0, vocab, size=plens[i]).tolist(),
            "max_new_tokens": olens[i],
            "arrival_s": round(t, 6)})
    return out


def train_shape(mix: Dict[str, Any], chips: int) -> Dict[str, int]:
    """A training mix is a batch shape; the rows are the program's own
    seeded synthetic stream."""
    if mix.get("kind") != "train":
        raise ValueError(f"not a train mix: kind={mix.get('kind')!r}")
    if int(mix["mesh_data"]) != chips:
        raise ValueError(
            f"mix shards over {mix['mesh_data']} chips, cell has {chips}")
    return {"rows_per_chip": int(mix["rows_per_chip"]),
            "seq_len": int(mix["seq_len"]),
            "global_batch": int(mix["rows_per_chip"]) * chips}
