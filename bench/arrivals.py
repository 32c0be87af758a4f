"""The one traffic generator: a traffic file's parameters and a seed in, a
schedule of requests out, made whole before the window opens.

Every seed gets the same schedule of sizes and due times; the seed picks
the prompts' token ids (and, elsewhere, the weights):

* lengths are quantiles of a log-normal with the file's median and sigma,
  rounded and clipped to ``[min, max]``, at ``(i + 0.5) / n`` for each of
  the ``n`` requests (set-up warms every prompt length the window sends);
* open-loop gaps come from the file's arrival process, scaled so that
  exactly ``round(rate * seconds)`` requests fall due inside the window;
* the gaps and the order of the lengths are drawn from one fixed stream.
  Near the knee the order alone decides the queue: with the order drawn
  from the seed, the time-to-first-token tail of one cell read 3.1 s to
  10.2 s across seeds while each seed repeated within 3%.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist

import numpy as np

# gaps and the order of lengths come from this stream, never from the seed
_STREAM = 20_240_521


@dataclass(frozen=True)
class Req:
    idx: int
    prompt: np.ndarray        # token ids
    max_new: int
    due: float | None         # seconds after the window opens (open loop)

    @property
    def prompt_len(self) -> int:
        return int(self.prompt.shape[0])


def seed_rng(seed: int) -> np.random.Generator:
    return np.random.default_rng(int(seed) % 2**64)


def length_quantiles(spec: dict, n: int) -> np.ndarray:
    """``n`` lengths: log-normal quantiles, rounded, clipped."""
    nd = NormalDist()
    mu, sigma = math.log(spec["median"]), float(spec["sigma"])
    out = [round(math.exp(mu + sigma * nd.inv_cdf((i + 0.5) / n)))
           for i in range(n)]
    return np.clip(np.asarray(out, np.int64), spec["min"], spec["max"])


def gaps(arrivals: dict, n: int, seconds: float,
         rng: np.random.Generator) -> np.ndarray:
    """``n`` inter-arrival gaps summing to ``seconds``."""
    process = arrivals["process"]
    if process == "poisson":
        g = rng.exponential(1.0, n)
    elif process == "gamma":          # coefficient of variation ``cv``
        shape = 1.0 / float(arrivals["cv"]) ** 2
        g = rng.gamma(shape, 1.0, n)
    else:
        raise ValueError(f"unknown arrival process {process!r}")
    return g * (seconds / g.sum())


def n_open(traffic: dict, seconds: float) -> int:
    return max(1, round(float(traffic["arrivals"]["rate_per_s"]) * seconds))


def check_fits(traffic: dict, kv_len: int) -> None:
    worst = traffic["prompt"]["max"] + traffic["output"]["max"]
    if worst > kv_len:
        raise ValueError(f"prompt max + output max = {worst} exceeds "
                         f"kv_len {kv_len}: such a request would be refused")


def _requests(traffic: dict, n: int, order: np.random.Generator,
              ids: np.random.Generator, vocab: int, dues) -> list[Req]:
    prompts = order.permutation(length_quantiles(traffic["prompt"], n))
    outs = order.permutation(length_quantiles(traffic["output"], n))
    return [Req(idx=i, prompt=ids.integers(0, vocab, int(prompts[i]),
                                           dtype=np.int32),
                max_new=int(outs[i]),
                due=None if dues is None else float(dues[i]))
            for i in range(n)]


def schedule(traffic: dict, seed: int, seconds: float, vocab: int,
             kv_len: int) -> list[Req]:
    """Open loop: every request due in ``[0, seconds)``, by due time.
    Closed loop: the pool of ``traffic["pool"]`` requests the clients take
    in turn (``due`` is set when a client sends it)."""
    check_fits(traffic, kv_len)
    order, ids = np.random.default_rng(_STREAM), seed_rng(seed)
    if traffic["loop"] == "closed":
        return _requests(traffic, int(traffic["pool"]), order, ids, vocab,
                         None)
    n = n_open(traffic, seconds)
    g = gaps(traffic["arrivals"], n, seconds, order)
    dues = np.concatenate([[0.0], np.cumsum(g)[:-1]])
    return _requests(traffic, n, order, ids, vocab, dues)


def prompt_lengths(traffic: dict, seconds: float) -> list[int]:
    """Every prompt length a window of this traffic can send, whatever the
    seed: the shapes set-up has to warm."""
    n = (int(traffic["pool"]) if traffic["loop"] == "closed"
         else n_open(traffic, seconds))
    return sorted({int(x) for x in length_quantiles(traffic["prompt"], n)})
