"""The one traffic generator: an open-loop Poisson stream whose sizes and
gaps are a fixed multiset and whose order comes from the seed.

A mix file gives lognormal prompt and output lengths (median, sigma,
clipped to [min, max]) and ``round``, a number ``K`` of requests.  One
round is ``K`` (prompt, output) pairs: the prompt lognormal's quantiles
at ``(j + 0.5) / K`` paired with the output lognormal's quantiles in a
fixed shuffled order (``PAIRING_SEED``), so a round is a stratified
sample of the mix with its longest prompt and its longest output in it.
For a window of ``seconds`` at ``rate`` requests per second the stream
holds ``N = max(round(rate * seconds), K)`` requests:

* ``N // K`` whole rounds one after another, each in a seeded order,
  then ``N % K`` pairs of one more round, taken evenly over its prompt
  quantiles (the same pairs for every seed);
* gaps between arrivals that are the exponential's quantiles in a
  seeded order, scaled so the last request is due before the window
  closes.

The output check compares one copy of each of the round's ``K`` pairs,
the copy chosen by the seed: the check requests are drawn from the
window's own requests, counted among its ``N``, and the number of
tokens compared is the sum of one round's outputs whatever the seed,
the window's length or how much the window serves.  Every seed serves
the same sizes in another order.  The Poisson stream of
``repro.sched.arrivals.poisson_arrivals`` draws its gaps freely; here
they are stratified for steadiness from run to run.
"""
from __future__ import annotations

from dataclasses import dataclass
from statistics import NormalDist
from typing import List, Tuple

import numpy as np

#: token ids are drawn from [FIRST_ID, vocab): ids below it are the
#: program's filler and special ids
FIRST_ID = 10

#: the fixed pairing of prompt and output quantiles within a round
PAIRING_SEED = 0


@dataclass
class Planned:
    rid: int
    due: float              # seconds after the window opens
    prompt: np.ndarray      # int32 token ids
    max_new: int
    check: bool


def _lognormal_quantiles(dist: dict, n: int) -> List[int]:
    z = [NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)]
    return [int(np.clip(round(dist["median"] * np.exp(dist["sigma"] * q)),
                        dist["min"], dist["max"])) for q in z]


def round_pairs(mix: dict) -> List[Tuple[int, int]]:
    """One round's (prompt tokens, output tokens), by prompt quantile."""
    k = int(mix["round"])
    prompts = _lognormal_quantiles(mix["prompt_tokens"], k)
    outputs = _lognormal_quantiles(mix["output_tokens"], k)
    order = np.random.default_rng(PAIRING_SEED).permutation(k)
    return [(prompts[j], outputs[int(order[j])]) for j in range(k)]


def compared_tokens(mix: dict) -> int:
    """Tokens the output check compares in every run of this mix."""
    return sum(new for _, new in round_pairs(mix))


def plan(mix: dict, rate: float, seconds: float, seed: int,
         vocab: int) -> List[Planned]:
    """The window's requests in order of arrival."""
    if mix.get("arrivals") != "poisson":
        raise ValueError(f"unknown arrival process {mix.get('arrivals')!r}")
    pairs = round_pairs(mix)
    k = len(pairs)
    n = max(int(round(rate * seconds)), k)
    whole, part = divmod(n, k)
    rng = np.random.default_rng(seed)
    # (pair index, copy) of every request, in order of arrival
    slots = []
    for r in range(whole):
        slots += [(int(j), r) for j in rng.permutation(k)]
    last = [int(round((i + 0.5) * k / part - 0.5)) for i in range(part)]
    slots += [(last[int(i)], whole) for i in rng.permutation(part)]
    copies = [whole + (j in last) for j in range(k)]
    checked = {(j, int(rng.integers(copies[j]))) for j in range(k)}
    gaps = -np.log(1.0 - (np.arange(n - 1) + 0.5) / (n - 1))
    gaps = rng.permutation(gaps)
    # the last arrival is due half a mean gap before the close
    gaps *= seconds * (1.0 - 0.5 / n) / gaps.sum()
    due = np.concatenate([[0.0], np.cumsum(gaps)])
    out = []
    for i, (j, copy) in enumerate(slots):
        plen, new = pairs[j]
        out.append(Planned(rid=i, due=float(due[i]),
                           prompt=rng.integers(FIRST_ID, vocab, plen,
                                               dtype=np.int32),
                           max_new=int(new), check=(j, copy) in checked))
    return out
