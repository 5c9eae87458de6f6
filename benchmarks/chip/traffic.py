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
- the parameters of each request's seeded conditioning, which the
  configuration's family reads (``families/<name>.py`` ``conds``; DiT's
  is ``cond_std``, the spread of its conditioning vector).

Open-loop arrivals are the same for every seed: the ``round(rate x
seconds)`` gaps are the quantiles of the exponential distribution at
``(k + 1/2) / n``, in the order the traffic file's ``order_seed`` draws.
The tail of one window moves with where the bursts fall far more than
with anything the run's seed changes, so the seed draws each request's
contents (its conditioning), not its time.
"""

from __future__ import annotations

import dataclasses
from typing import Callable

import numpy as np

#: ``draw(gen, n)``: the conditioning of ``n`` requests from a generator
Draw = Callable[[np.random.Generator, int], list]


@dataclasses.dataclass
class Request:
    rid: int
    due: float              # seconds after the window opens
    cond: object            # the family's conditioning pytree


def rng(seed: int, stream: str) -> np.random.Generator:
    """An independent generator per use, all from the run's seed."""
    return np.random.default_rng([int(seed), *stream.encode()])


def open_loop(traffic: dict, seed: int, seconds: float,
              draw: Draw) -> list[Request]:
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
    conds = draw(rng(seed, "cond"), n)
    return [Request(i, float(dues[i]), conds[i]) for i in range(n)]


class Backlog:
    """Requests on demand for a queue kept ``min_pending`` deep."""

    def __init__(self, traffic: dict, seed: int, draw: Draw):
        self.min_pending = int(traffic["arrivals"]["min_pending"])
        self._cond_gen = rng(seed, "cond")
        self._draw = draw
        self.next_rid = 0

    def take(self, k: int, now: float) -> list[Request]:
        out = []
        for _ in range(k):
            (cond,) = self._draw(self._cond_gen, 1)
            out.append(Request(self.next_rid, now, cond))
            self.next_rid += 1
        return out
