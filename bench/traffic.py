"""The one request generator: a traffic file of parameters and a seed in,
an open-loop schedule of requests out.

Every seed gets the same schedule: ``n = rate * seconds`` requests whose
inter-arrival gaps are the ``n`` evenly spaced quantiles of the
exponential distribution (Poisson arrivals), scaled to fill the window
exactly, and whose prompt and output lengths are the quantiles of their
clipped lognormals, each list in one shuffled order fixed by the traffic
file.  The seed draws the token ids (and the benchmark's weights), so two
seeds differ in content but not in the work they ask for, nor in when:
with a few dozen requests in a window, a per-seed order would change the
work the window sees far more than any program change.

Traffic file keys: ``rate_per_s``; ``prompt_len`` and ``output_len``:
``{"median", "sigma", "min", "max"}``; ``max_total`` (prompt + output);
``token_ids`` (ids are drawn from ``[0, token_ids)``); optional
``shared_prefix``: ``{"tokens", "groups"}``, each prompt then starting
with one of ``groups`` common prefixes of that many tokens; optional
``prime``: ``{"live"}``, the requests live in the mix's steady state when
the window opens (``prime``).
"""
from __future__ import annotations

import dataclasses
import statistics

import numpy as np


@dataclasses.dataclass
class Request:
    rid: int
    at_s: float              # scheduled arrival, from the window's start
    prompt: np.ndarray       # int64 token ids
    max_new: int


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(spec: dict, n: int) -> np.ndarray:
    z = np.asarray([statistics.NormalDist().inv_cdf(q)
                    for q in _quantiles(n)])
    x = np.rint(spec["median"] * np.exp(spec["sigma"] * z))
    return np.clip(x, spec["min"], spec["max"]).astype(int)


def schedule(tr: dict, seed: int, seconds: float):
    n = max(1, int(round(tr["rate_per_s"] * seconds)))
    order = np.random.default_rng(0)            # the same for every seed
    gaps = -np.log1p(-_quantiles(n))
    gaps = order.permutation(gaps * (seconds / gaps.sum()))
    at = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    plen = order.permutation(lengths(tr["prompt_len"], n))
    olen = order.permutation(lengths(tr["output_len"], n))
    rng = np.random.default_rng([seed, 3])
    olen = np.minimum(olen, tr["max_total"] - plen)
    shared = tr.get("shared_prefix")
    prefixes = ([rng.integers(0, tr["token_ids"], shared["tokens"])
                 for _ in range(shared["groups"])] if shared else [])
    out = []
    for i in range(n):
        own = rng.integers(0, tr["token_ids"], plen[i])
        if prefixes:
            pre = prefixes[rng.integers(len(prefixes))]
            own = np.concatenate([pre, own[len(pre):]])[:plen[i]]
        out.append(Request(i, float(at[i]), own.astype(np.int64),
                           int(olen[i])))
    return out


def prime(tr: dict, seed: int):
    """The requests that the steady state of the mix has in flight when the
    window opens: ``prime.live`` of them (Little's law: the rate times the
    time a request lives), each drawn in proportion to how long it lives,
    about a tick per prefill chunk and per output token, and caught at a
    point of its life spread evenly over the set.  The tokens served before
    that point join its prompt; it asks for the rest of its output.  The
    sizes are the same for every seed, longest remaining output first;
    the seed draws the token ids.  ``rid``s are negative."""
    spec = tr.get("prime")
    if not spec:
        return []
    n = int(spec["live"])
    k = 64 * n
    order = np.random.default_rng(1)            # the same for every seed
    plen = order.permutation(lengths(tr["prompt_len"], k))
    olen = order.permutation(lengths(tr["output_len"], k))
    olen = np.minimum(olen, tr["max_total"] - plen)
    life = olen + np.ceil(plen / tr["engine"]["prefill_chunk"])
    pick = order.choice(k, n, replace=False, p=life / life.sum())
    served = np.floor(order.permutation(_quantiles(n)) * olen[pick])
    served = served.astype(int)
    rest = olen[pick] - served
    rng = np.random.default_rng([seed, 4])
    out = []
    for j, i in enumerate(np.argsort(-rest, kind="stable")):
        ctx = plen[pick[i]] + served[i]
        out.append(Request(-1 - j, 0.0,
                           rng.integers(0, tr["token_ids"], ctx).astype(
                               np.int64), int(rest[i])))
    return out
