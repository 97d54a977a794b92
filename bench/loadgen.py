"""The one traffic generator. A traffic mix is a data file,
``bench/traffic/<name>.json``, whose ``requests`` entry this module reads:

    "requests": {"prompt_len": 128, "output_len": 128,
                 "arrival": {"kind": "closed"}}

a closed loop with a full queue, every request with one prompt length and
one output length. The run's seed draws the token ids, so every seed does
the same work. Open-loop arrivals and mixed lengths come with a driver
that can serve them.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np


@dataclass(frozen=True)
class Request:
    index: int
    prompt: np.ndarray      # int32 token ids
    output_len: int


def lengths(traffic: dict) -> tuple[int, int]:
    """(prompt length, output length) of the mix."""
    spec = traffic["requests"]
    plen, olen = spec["prompt_len"], spec["output_len"]
    if spec["arrival"] != {"kind": "closed"} or not (
            isinstance(plen, int) and isinstance(olen, int)):
        raise ValueError("traffic is closed-loop, of one prompt and one "
                         "output length")
    return plen, olen


def requests(traffic: dict, seed, n: int, vocab: int) -> list[Request]:
    """``n`` requests of the mix for ``seed`` (a whole number, or a tuple
    of them, as ``numpy.random.default_rng`` takes)."""
    plen, olen = lengths(traffic)
    rng = np.random.default_rng(seed)
    return [Request(i, rng.integers(0, vocab, size=plen, dtype=np.int32),
                    olen) for i in range(n)]
