"""The one traffic generator: every mix is a data file of parameters
(``bench/traffic/<mix>.json``) that this module turns into work from the seed.

Two kinds of mix:

``statements``  a closed loop of SQL statements, issued back to back by one
                session. ``statements`` lists templates (``{table}``/``{udf}``
                are filled from the configuration), run in turn from a start
                the seed picks. Each statement also gets a seed of its own (a
                TRAIN statement draws its initial coefficients from it).

``open_loop``   requests due on a fixed schedule whether or not earlier ones
                finished. ``rate_per_s`` is the offered rate; ``ramp_s`` of
                arrivals run before the window. Prompt and output lengths are
                lognormal (``median``, ``sigma``, clipped to ``[min, max]``);
                prompt token ids are uniform over the vocabulary. The
                serving system reads ``trace_s`` (the traced part of the
                window) and ``first_token_wait_s`` (how long past the
                window's close its requests are followed to their first
                token; see ``bench/systems/serving.py``).

Every seed gets the same multiset of gaps and lengths, in another order: the
gaps are the exponential distribution's quantiles at (i + 1/2)/n and the
lengths the lognormal's, each permuted by the seed, for the ramp and for the
window apart. So two seeds offer the window the same work and differ only in
its order and in the token ids.
"""
from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np


def statement_seed(seed: int, k: int) -> int:
    return int(np.random.SeedSequence([int(seed), int(k)]).generate_state(1)[0])


def statements(mix: dict, seed: int, fill: dict):
    """Endless (k, sql, statement_seed) for a ``statements`` mix."""
    if mix["kind"] != "statements":
        raise ValueError(f"mix kind {mix['kind']!r} is not 'statements'")
    texts = [t.format(**fill) for t in mix["statements"]]
    start = int(np.random.default_rng(seed).integers(len(texts)))
    k = 0
    while True:
        yield k, texts[(start + k) % len(texts)], statement_seed(seed, k)
        k += 1


def _quantiles(n: int, ppf) -> np.ndarray:
    return np.array([ppf((i + 0.5) / n) for i in range(n)])


def lognormal_lengths(spec: dict, n: int) -> np.ndarray:
    """The n quantiles of a clipped lognormal, ascending."""
    nd = NormalDist(math.log(spec["median"]), spec["sigma"])
    q = np.exp(_quantiles(n, nd.inv_cdf))
    return np.clip(np.rint(q), spec["min"], spec["max"]).astype(np.int64)


def _phase(mix: dict, rng, n: int, t0: float) -> list[dict]:
    """``n`` requests from ``t0`` on: the exponential gaps' and the
    lognormal lengths' n quantiles, each permuted by ``rng``."""
    rate = float(mix["rate_per_s"])
    gaps = rng.permutation(_quantiles(n, lambda u: -math.log(1.0 - u) / rate))
    plen = rng.permutation(lognormal_lengths(mix["prompt"], n))
    nout = rng.permutation(lognormal_lengths(mix["output"], n))
    due = t0 + np.cumsum(gaps) - gaps[0]
    return [{"due": float(due[i]), "plen": int(plen[i]),
             "max_new_tokens": int(nout[i])} for i in range(n)]


def open_loop(mix: dict, seed: int, seconds: float, vocab: int) -> list[dict]:
    """Requests due from ``-ramp_s`` to ``seconds`` (seconds relative to the
    window's start), each ``{rid, due, prompt, max_new_tokens}``, in due
    order. The ramp and the window are drawn apart, so every seed offers the
    window the same requests, in another order."""
    if mix["kind"] != "open_loop":
        raise ValueError(f"mix kind {mix['kind']!r} is not 'open_loop'")
    rate, ramp = float(mix["rate_per_s"]), float(mix["ramp_s"])
    rng = np.random.default_rng(seed)
    reqs = (_phase(mix, rng, max(1, math.ceil(rate * ramp)), -ramp)
            + _phase(mix, rng, max(1, math.ceil(rate * float(seconds))), 0.0))
    reqs.sort(key=lambda r: r["due"])
    for i, r in enumerate(reqs):
        r["rid"] = i
        r["prompt"] = rng.integers(0, vocab, r.pop("plen")).tolist()
    return reqs
