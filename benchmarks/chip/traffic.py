"""The one traffic generator: a traffic file's parameters and a seed in,
the requests of a run out.

A traffic file (``traffic/<name>.json``) holds

- ``server``: the scheduler the cell serves with (``{"scheduler":
  "solve", "bucket_sizes": [...]}`` or ``{"scheduler": "step", "lanes":
  n}``);
- ``arrivals``: ``{"kind": "poisson", "rate_per_s": r, "order_seed":
  s}`` for an open loop, or ``{"kind": "backlog", "min_pending": n}``
  for a queue that is kept at least ``n`` deep;
- ``request``: what every request of the cell asks for, ``{"sampler":
  name, "nfe": n, ...solver fields..., "guidance_scale": g}``;
- ``cond_std``: the spread of each request's seeded conditioning vector.

Open-loop arrivals are the same for every seed: the ``round(rate x
seconds)`` gaps are the quantiles of the exponential distribution at
``(k + 1/2) / n``, in the order the traffic file's ``order_seed`` draws.
The tail of one window moves with where the bursts fall far more than
with anything the run's seed changes, so the seed draws each request's
contents (its conditioning vector), not its time.
"""

from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class Request:
    rid: int
    due: float              # seconds after the window opens
    cond: np.ndarray        # [latent_dim] float32


def rng(seed: int, stream: str) -> np.random.Generator:
    """An independent generator per use, all from the run's seed."""
    return np.random.default_rng([int(seed), *stream.encode()])


def open_loop(traffic: dict, seed: int, seconds: float,
              latent_dim: int) -> list[Request]:
    """The requests due in a window of ``seconds``, in due order."""
    arr = traffic["arrivals"]
    if arr["kind"] != "poisson":
        raise ValueError(f"open_loop takes poisson arrivals, not "
                         f"{arr['kind']!r}")
    rate = float(arr["rate_per_s"])
    n = max(1, int(round(rate * seconds)))
    gen = rng(arr["order_seed"], "arrivals")
    gaps = gen.permutation(-np.log1p(-(np.arange(n) + 0.5) / n) / rate)
    dues = np.cumsum(gaps)
    conds = rng(seed, "cond").normal(
        0.0, traffic["cond_std"], (n, latent_dim)).astype(np.float32)
    return [Request(i, float(dues[i]), conds[i]) for i in range(n)]


class Backlog:
    """Requests on demand for a queue kept ``min_pending`` deep."""

    def __init__(self, traffic: dict, seed: int, latent_dim: int):
        self.traffic = traffic
        self.min_pending = int(traffic["arrivals"]["min_pending"])
        self._cond_gen = rng(seed, "cond")
        self._dim = latent_dim
        self.next_rid = 0

    def take(self, k: int, now: float) -> list[Request]:
        out = []
        for _ in range(k):
            cond = self._cond_gen.normal(0.0, self.traffic["cond_std"],
                                         self._dim).astype(np.float32)
            out.append(Request(self.next_rid, now, cond))
            self.next_rid += 1
        return out
