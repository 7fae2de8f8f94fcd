"""The one general traffic generator. A traffic mix is a data file under
`traffic/`; this module turns it and `--seed` into requests. A later PR adds
a mix by adding a file, never by adding code here.

Steadiness: every seed gives the same SET of request sizes and of arrival
gaps, in another order. Sizes are the stratified quantiles of the stated
distribution (a pool of `pool` values, 64 unless the file says otherwise),
prompt and answer lengths paired once by the file's own `pairing_seed`; the
run's seed shuffles each pass through the pool and draws the token ids. So
two seeds offer the same work, and a difference between runs is the
system's, not the sample's.

A traffic file holds:
  driver         the driver program that offers it (a path under benchmark/)
  loop           "closed" with `clients`, or "open" with `rate_req_s` and
                 `arrivals` ("poisson", or "bursts" with `burst_size` and
                 `burst_window_s`)
  prompt_tokens, max_tokens
                 {"dist": "lognormal", "median", "sigma", "min", "max"} or
                 {"dist": "uniform", "min", "max"}
  sampling       fields copied into every request body
  preload_s      seconds of the same traffic before the window opens
  drain_s        longest wait, after the window, for its requests to end
  warmup         lone requests that build every program the mix can reach
"""

from __future__ import annotations

import math
import random
from statistics import NormalDist

DEFAULT_POOL = 64


def quantiles(dist: dict, n: int) -> list[int]:
    """n stratified quantiles of a length distribution, clipped to its
    [min, max] and rounded to whole tokens."""
    us = [(i + 0.5) / n for i in range(n)]
    if dist["dist"] == "lognormal":
        nd = NormalDist()
        xs = [dist["median"] * math.exp(dist["sigma"] * nd.inv_cdf(u))
              for u in us]
    elif dist["dist"] == "uniform":
        xs = [dist["min"] + u * (dist["max"] - dist["min"]) for u in us]
    else:
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    return [int(round(min(max(x, dist["min"]), dist["max"]))) for x in xs]


def size_pool(traffic: dict) -> list[tuple[int, int]]:
    """The fixed set of (prompt tokens, max_tokens) pairs of a mix."""
    n = int(traffic.get("pool", DEFAULT_POOL))
    prompts = quantiles(traffic["prompt_tokens"], n)
    answers = quantiles(traffic["max_tokens"], n)
    random.Random(int(traffic.get("pairing_seed", 0))).shuffle(answers)
    return list(zip(prompts, answers))


def gap_pool(traffic: dict) -> list[float]:
    """The fixed set of gaps between arrivals of an open loop, in seconds,
    with mean exactly 1 / rate_req_s."""
    n = int(traffic.get("pool", DEFAULT_POOL))
    rate = float(traffic["rate_req_s"])
    kind = traffic.get("arrivals", "poisson")
    if kind == "poisson":
        gaps = [-math.log(1.0 - (i + 0.5) / n) for i in range(n)]
    elif kind == "bursts":
        size = int(traffic["burst_size"])
        inside = float(traffic["burst_window_s"]) / size
        between = size / rate - inside * (size - 1)
        gaps = [between if i % size == 0 else inside for i in range(n)]
        return gaps  # ordered: a burst is `size` arrivals in a row
    else:
        raise ValueError(f"unknown arrivals {kind!r}")
    scale = (n / rate) / sum(gaps)
    return [g * scale for g in gaps]


def requests(traffic: dict, vocab: int, seed: int):
    """Endless iterator of request dicts: `idx`, `prompt` (token ids),
    `body` (the JSON sent) and, in an open loop, `due_s` from the start of
    the schedule."""
    rng = random.Random(seed)
    sizes = size_pool(traffic)
    is_open = traffic["loop"] == "open"
    gaps = gap_pool(traffic) if is_open else None
    shuffle_gaps = is_open and traffic.get("arrivals", "poisson") == "poisson"
    sampling = traffic.get("sampling", {})
    idx, due = 0, 0.0
    while True:
        order = list(range(len(sizes)))
        rng.shuffle(order)
        gorder = list(range(len(sizes)))
        if shuffle_gaps:
            rng.shuffle(gorder)
        for k, j in enumerate(order):
            plen, max_tokens = sizes[j]
            prompt = [rng.randrange(vocab) for _ in range(plen)]
            body = {"prompt": prompt, "max_tokens": max_tokens,
                    "stream": True, **sampling,
                    "seed": (seed * 1000003 + idx) % 2147483647}
            req = {"idx": idx, "body": body}
            if is_open:
                due += gaps[gorder[k]]
                req["due_s"] = due
            yield req
            idx += 1


def warmup_bodies(traffic: dict, vocab: int) -> list[dict]:
    """The lone warm-up requests of a mix, from its `warmup` list. Each
    entry gives `prompt_tokens`, `max_tokens`, `temperature` and optionally
    `check` (true for the greedy requests the plain reference is held to).
    Their token ids come from a fixed seed: warm-up is the same in every
    run, so every run after the first finds every program in the cache."""
    rng = random.Random(20240607)
    out = []
    for w in traffic["warmup"]:
        temp = float(w["temperature"])
        body = {"prompt": [rng.randrange(vocab)
                           for _ in range(int(w["prompt_tokens"]))],
                "max_tokens": int(w["max_tokens"]), "stream": True,
                "temperature": temp, "top_k": 50 if temp else 0,
                "top_p": 1.0, "seed": 0}
        out.append({"body": body, "check": bool(w.get("check"))})
    return out
