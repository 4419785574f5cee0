"""One general traffic generator, driven by the mix files in
``bench/traffic/<mix>.json``.

Lengths follow a clipped lognormal (median, sigma, min, max). They are
drawn stratified: every block of ``strata`` consecutive requests holds
each of ``strata`` fixed quantiles of the law once, in an order drawn
from the seed (prompt and output quantiles permuted independently). So
every seed serves the same set of sizes, in another order, and a window
that completes some blocks sees the same work whatever the seed. Token
ids are uniform over the vocabulary.

Open-loop arrivals are Poisson at ``rate`` requests per second, drawn
the same way: the gaps of a block are the ``strata`` quantiles of the
exponential law, rescaled to mean exactly ``1 / rate``, permuted.
"""
from __future__ import annotations

import dataclasses
import json
import os
from statistics import NormalDist
from typing import Dict, Iterator, Optional

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


@dataclasses.dataclass(frozen=True)
class Lengths:
    median: float
    sigma: float
    min: int
    max: int

    def quantiles(self, n: int) -> np.ndarray:
        """The n stratum midpoints of the clipped lognormal, as ints."""
        nd = NormalDist()
        z = np.array([nd.inv_cdf((i + 0.5) / n) for i in range(n)])
        v = np.exp(np.log(self.median) + self.sigma * z)
        return np.clip(np.rint(v), self.min, self.max).astype(np.int64)


@dataclasses.dataclass(frozen=True)
class Mix:
    name: str
    loop: str                       # "closed" | "open"
    clients: int                    # closed loop: clients, each waits
    slots: int
    cache_len: int
    buckets: tuple
    prompt: Lengths
    output: Lengths
    strata: int
    lead_s: float                   # loop time before the window opens
    raw: Dict

    @staticmethod
    def load(name: str, directory: Optional[str] = None) -> "Mix":
        path = os.path.join(directory or os.path.join(HERE, "traffic"),
                            f"{name}.json")
        with open(path, encoding="utf-8") as fh:
            raw = json.load(fh)
        return Mix.from_dict(name, raw)

    @staticmethod
    def from_dict(name: str, raw: Dict) -> "Mix":
        mix = Mix(name=name, loop=raw["loop"],
                  clients=int(raw.get("clients", 0)),
                  slots=int(raw["slots"]), cache_len=int(raw["cache_len"]),
                  buckets=tuple(int(b) for b in raw["buckets"]),
                  prompt=Lengths(**raw["prompt"]),
                  output=Lengths(**raw["output"]),
                  strata=int(raw["strata"]),
                  lead_s=float(raw["lead_s"]), raw=raw)
        if mix.loop not in ("closed", "open"):
            raise ValueError(f"mix {name}: loop must be closed or open")
        if mix.prompt.max > mix.buckets[-1]:
            raise ValueError(f"mix {name}: prompts longer than the "
                             "largest bucket")
        if mix.prompt.max + mix.output.max > mix.cache_len:
            raise ValueError(f"mix {name}: prompt + output exceeds "
                             "cache_len (the KV ring would wrap)")
        return mix


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), stream])


def requests(mix: Mix, seed: int, vocab: int
             ) -> Iterator[Dict[str, object]]:
    """Endless stream of {"prompt": int32 ids, "max_new": int}."""
    rng = _rng(seed, 1)
    pq = mix.prompt.quantiles(mix.strata)
    oq = mix.output.quantiles(mix.strata)
    while True:
        for p, o in zip(rng.permutation(pq), rng.permutation(oq)):
            yield {"prompt": rng.integers(0, vocab, int(p)).astype(
                np.int32), "max_new": int(o)}


def arrival_gaps(mix: Mix, seed: int, rate: float) -> Iterator[float]:
    """Endless stream of seconds between open-loop arrivals."""
    rng = _rng(seed, 2)
    n = mix.strata
    q = -np.log(1.0 - (np.arange(n) + 0.5) / n)
    q = q / q.mean() / rate
    while True:
        yield from rng.permutation(q).tolist()
