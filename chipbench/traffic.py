"""One general generator for every traffic file under ``traffic/``.

Two kinds of mix:

* ``open_loop``: requests due on the wall clock, from ``lead_s`` before
  the window opens.  Inter-arrival gaps are
  exponential (``poisson``) or Gamma with shape ``1/burstiness``
  (``gamma``: same mean rate, clumped arrivals).  Prompt and output
  lengths are lognormal with the given distribution mean, clipped, and
  prompts rounded up to the next of ``round_up_to``.  (The length and
  arrival arithmetic follows ``repro.serve.workload``.)
* ``sessions``: a closed loop of long-context clients.  Set-up admits
  one session per client; in the window a client whose session ends
  starts its next one at once.  The first sessions' answer lengths are
  staggered, so that sessions end, and new ones are prefilled, at
  spread-out times.

The work does not depend on ``--seed``: arrival times and lengths are
drawn from the file's ``base_seed``, and the run's seed draws the prompt
tokens (and, elsewhere, the weights).  Every seed serves the same
schedule with other values.  (Shuffling the schedule by seed moved the
number of requests due in a 45 s window of the chat mix between 76 and
105, and its TTFT tail by 20x, more than any bound could hold.)
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List, Sequence

import numpy as np


@dataclasses.dataclass
class Planned:
    rid: int
    due_s: float            # seconds after the window opens
    prompt_len: int
    max_new: int
    tokens: List[int]


def seed_rng(seed: int, *salt: int) -> np.random.Generator:
    """A generator for any whole-number seed, large or negative."""
    return np.random.default_rng([abs(int(seed)), int(seed < 0), *salt])


def lognormal_lengths(rng, mean: float, sigma: float, lo: int, hi: int,
                      n: int) -> np.ndarray:
    # parameterised by the distribution's mean: mu = ln(mean) - sigma^2/2
    mu = math.log(mean) - 0.5 * sigma * sigma
    raw = rng.lognormal(mu, sigma, size=n)
    return np.clip(np.rint(raw), lo, hi).astype(np.int64)


def round_up(lengths: np.ndarray, values: Sequence[int]) -> np.ndarray:
    """Each length rounded up to the smallest of ``values`` that holds it."""
    vals = np.sort(np.asarray(values, np.int64))
    idx = np.searchsorted(vals, lengths, side="left")
    if (idx >= len(vals)).any():
        raise ValueError(f"a length above the largest rounding value "
                         f"{int(vals[-1])}: clip before rounding")
    return vals[idx]


def gaps(rng, arrivals: Dict[str, Any], n: int) -> np.ndarray:
    rate = float(arrivals["rate_per_s"])
    if arrivals["process"] == "poisson":
        return rng.exponential(1.0 / rate, size=n)
    if arrivals["process"] == "gamma":
        shape = 1.0 / float(arrivals["burstiness"])
        return rng.gamma(shape, 1.0 / (rate * shape), size=n)
    raise ValueError(f"unknown arrival process {arrivals['process']!r}")


def n_requests(traffic: Dict[str, Any], seconds: float) -> int:
    """Enough requests to cover the lead-in and the window, with room
    for a slow draw."""
    rate = float(traffic["arrivals"]["rate_per_s"])
    span = seconds + float(traffic.get("lead_s", 0.0))
    return int(math.ceil(rate * span * 1.5)) + 32


def prompt_tokens(seed: int, rid: int, n: int, vocab: int,
                  salt: int = 1) -> List[int]:
    return seed_rng(seed, salt, rid).integers(0, vocab, size=n).tolist()


def open_loop(traffic: Dict[str, Any], seed: int, seconds: float,
              vocab: int) -> List[Planned]:
    n = n_requests(traffic, seconds)
    base = np.random.default_rng(int(traffic["base_seed"]))
    g = gaps(base, traffic["arrivals"], n)
    # hold the drawn schedule to its stated rate: the requests due by the
    # window's close arrive at exactly rate_per_s on average
    rate = float(traffic["arrivals"]["rate_per_s"])
    m = int(round(rate * (seconds + float(traffic.get("lead_s", 0.0)))))
    g *= (m / rate) / g[:m].sum()
    p, o = traffic["prompt"], traffic["output"]
    plens = lognormal_lengths(base, p["mean"], p["sigma"], p["lo"], p["hi"], n)
    if p.get("round_up_to"):
        plens = round_up(plens, p["round_up_to"])
    outs = lognormal_lengths(base, o["mean"], o["sigma"], o["lo"], o["hi"], n)
    due = np.cumsum(g)
    return [Planned(i, float(due[i]), int(plens[i]), int(outs[i]),
                    prompt_tokens(seed, i, int(plens[i]), vocab))
            for i in range(n)]


def sessions(traffic: Dict[str, Any], seed: int, vocab: int) -> List[Planned]:
    """Each client's first session: client ``i`` has the i-th (context,
    answer) pair of ``contexts`` x ``max_new``, and its first answer is
    ``first_max_new[i]``, staggered so that the first sessions end at
    spread-out times rather than together."""
    s = traffic["sessions"]
    firsts = s["first_max_new"]
    return [Planned(i, 0.0, c, int(firsts[i]), prompt_tokens(seed, i, c, vocab))
            for i, (c, _) in enumerate(clients(traffic))]


def clients(traffic: Dict[str, Any]) -> List[tuple]:
    s = traffic["sessions"]
    return [(int(c), int(n)) for c in s["contexts"] for n in s["max_new"]]


def next_session(traffic: Dict[str, Any], client: int, rid: int, seed: int,
                 vocab: int) -> Planned:
    """The client's next session: its context length and answer length."""
    c, n = clients(traffic)[client]
    return Planned(rid, 0.0, c, n, prompt_tokens(seed, rid, c, vocab))


def plan(traffic: Dict[str, Any], seed: int, seconds: float,
         vocab: int) -> List[Planned]:
    if traffic["kind"] == "open_loop":
        return open_loop(traffic, seed, seconds, vocab)
    if traffic["kind"] == "sessions":
        return sessions(traffic, seed, vocab)
    raise ValueError(f"unknown traffic kind {traffic['kind']!r}")


def warm_shapes(traffic: Dict[str, Any]) -> List[tuple]:
    """(prompt length, group size) pairs the window can admit: every
    rounding value at every allowed group size, or the session groups."""
    if traffic["kind"] == "sessions":
        return [(int(c), 1) for c in traffic["sessions"]["contexts"]]
    return [(int(L), int(k)) for L in traffic["prompt"]["round_up_to"]
            for k in traffic["group_sizes"]]
