"""Traffic and tenant-fleet generation from a seed.

One general generator reads a traffic mix (``traffic/<name>.json``) and
yields the requests that the mix's clients send, in the order they send
them. Every length and every owner comes from a *deck*: a fixed multiset
of values, dealt in an order drawn from the seed and dealt again,
reshuffled, when it runs out. So every seed sends the same set of sizes
and the same tenant shares, in another order, and runs of different
seeds do the same work.

- Lengths: ``levels`` quantiles of a lognormal (median, sigma), clipped
  to [min, max] and rounded. ``levels: 1`` with min == max is a fixed
  length.
- Owners: a Zipf law with exponent ``zipf_s`` over the tenants in the
  order listed (the first is the most popular), with ``base_share`` of
  the requests sent to the base model (owner None). Each owner's count
  in a deck of ``deck`` requests is its share rounded by largest
  remainder, so the shares hold to within one request per deck.
- Prompt tokens: uniform over the vocabulary, from the seed and the
  request's index.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist
from typing import Iterator, List, Optional

import numpy as np

# stream ids keep the decks of one seed independent of each other
_PROMPT, _OUTPUT, _OWNER, _TOKENS = 1, 2, 3, 4


def seed_words(seed: int) -> list:
    """A whole-number seed of any size as 32-bit words (low first)."""
    s = int(seed) % (1 << 64)
    return [s & 0xFFFFFFFF, s >> 32]


def _rng(seed: int, *parts: int) -> np.random.Generator:
    return np.random.default_rng(seed_words(seed) + [int(p) for p in parts])


def length_levels(spec: dict) -> List[int]:
    """The deck of lengths a mix's length spec deals: ``levels`` lognormal
    quantiles at (i + 0.5) / levels, clipped to [min, max], rounded."""
    lo, hi, n = int(spec["min"]), int(spec["max"]), int(spec["levels"])
    if not 1 <= lo <= hi or n < 1:
        raise ValueError(f"bad length spec {spec}")
    median = float(spec.get("median", lo))
    sigma = float(spec.get("sigma", 0.0))
    nd = NormalDist()
    out = []
    for i in range(n):
        z = nd.inv_cdf((i + 0.5) / n) if n > 1 else 0.0
        v = median * math.exp(sigma * z)
        out.append(int(min(hi, max(lo, round(v)))))
    return out


def zipf_shares(n_tenants: int, s: float, base_share: float) -> List[float]:
    """[base, tenant0, tenant1, ...] request shares."""
    w = np.array([(r + 1) ** -s for r in range(n_tenants)], np.float64)
    return [base_share] + list((1.0 - base_share) * w / w.sum())


def owner_deck(n_tenants: int, s: float, base_share: float,
               deck: int) -> List[int]:
    """Owner indices (-1 = base model, else tenant rank) of one deck,
    each owner's count its share of ``deck`` by largest remainder."""
    shares = zipf_shares(n_tenants, s, base_share)
    exact = [p * deck for p in shares]
    counts = [int(math.floor(e)) for e in exact]
    by_rem = sorted(range(len(exact)),
                    key=lambda i: (counts[i] - exact[i], i))
    for i in by_rem[:deck - sum(counts)]:
        counts[i] += 1
    out = []
    for i, c in enumerate(counts):
        out += [i - 1] * c
    return out


def _dealt(values: list, seed: int, stream: int) -> Iterator:
    """Endless: the deck shuffled by (seed, stream, round), round after
    round."""
    k = 0
    while True:
        order = _rng(seed, stream, k).permutation(len(values))
        for i in order:
            yield values[i]
        k += 1


@dataclass
class RequestSpec:
    index: int
    owner: Optional[str]       # tenant name, None = base model
    prompt: np.ndarray         # int32 [prompt_len]
    max_new_tokens: int


class Traffic:
    """The request stream of one mix under one seed.

    ``next()`` returns the k-th request of the stream; a closed-loop
    client calls it when its previous request finished, so what is sent
    does not depend on timing, only when.
    """

    def __init__(self, mix: dict, tenant_names: List[str], vocab: int,
                 seed: int):
        fleet = mix["fleet"]
        if len(tenant_names) != len(fleet["tenants"]):
            raise ValueError("tenant names do not match the fleet")
        self.mix = mix
        self.seed = int(seed)
        self.vocab = int(vocab)
        self.names = list(tenant_names)
        self.prompt_levels = length_levels(mix["prompt_len"])
        self.output_levels = length_levels(mix["output_len"])
        self.owners = owner_deck(len(tenant_names), float(fleet["zipf_s"]),
                                 float(fleet["base_share"]),
                                 int(fleet["deck"]))
        self._p = _dealt(self.prompt_levels, self.seed, _PROMPT)
        self._o = _dealt(self.output_levels, self.seed, _OUTPUT)
        self._w = _dealt(self.owners, self.seed, _OWNER)
        self.sent = 0

    def next(self) -> RequestSpec:
        k = self.sent
        self.sent += 1
        L = int(next(self._p))
        owner = int(next(self._w))
        toks = _rng(self.seed, _TOKENS, k).integers(
            0, self.vocab, size=L, dtype=np.int64).astype(np.int32)
        return RequestSpec(index=k,
                           owner=None if owner < 0 else self.names[owner],
                           prompt=toks, max_new_tokens=int(next(self._o)))

    def max_seq(self) -> int:
        """Positions a slot must hold: the longest prompt plus the
        longest output."""
        return max(self.prompt_levels) + max(self.output_levels)


def tenant_names(mix: dict) -> List[str]:
    return [f"tenant{i}" for i in range(len(mix["fleet"]["tenants"]))]
