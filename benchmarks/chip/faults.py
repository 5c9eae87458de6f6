"""Faults planted in the program under test, to show that the check
catches them. Neither the benchmark's runs nor the reference use this:
``calibrate.py`` reads them on the chip, ``test_bench.py`` on the CPU.

``plant(name)`` patches the program and returns a function that undoes
the patch.

- ``stuck``: every solver step returns its state unchanged.
- ``shifted``: every answer goes to the request served after it.
- ``lane``: one lane of eight reads its neighbour's answer, a wrong lane
  join: lane 3 of each solve microbatch, or of each continuous batch as
  it is harvested, returns lane 2's latent. Requests in the other lanes
  are served as they should be, so the median of a sample hides it.
"""

from __future__ import annotations

import dataclasses

#: the lane that reads its neighbour's answer under ``lane``
LANE = 3
#: rids of the requests the ``lane`` fault reached, for the readings
REACHED: set = set()


def _patch(owner, name, fn):
    orig = getattr(owner, name)
    setattr(owner, name, fn(orig))
    return lambda: setattr(owner, name, orig)


def plant(name: str):
    if name == "stuck":
        from repro.core.samplers import multistep

        def stuck(orig):
            def f(combine, cdt, decay_i, x_prev, coeffs, buf, noise_i, xi):
                orig(combine, cdt, decay_i, x_prev, coeffs, buf, noise_i, xi)
                return x_prev
            return f
        return _patch(multistep, "_combine_rows", stuck)
    if name == "shifted":
        from repro.serve.engine import ServeEngine
        held = []

        def shifted(orig):
            def f(self):
                out = []
                for r in orig(self):
                    if r.x0 is not None:
                        held.append(r.x0)
                        r = dataclasses.replace(
                            r, x0=held[-2] if len(held) > 1 else 0.0 * r.x0)
                    out.append(r)
                return out
            return f
        return _patch(ServeEngine, "step", shifted)
    if name == "lane":
        from repro.serve.continuous import ContinuousBatcher
        from repro.serve.engine import ServeEngine

        def solve_lane(orig):
            def f(self, mb):
                out = orig(self, mb)
                if len(out) > LANE and out[LANE].x0 is not None:
                    REACHED.add(out[LANE].rid)
                    out[LANE] = dataclasses.replace(out[LANE],
                                                    x0=out[LANE - 1].x0)
                return out
            return f

        def step_lane(orig):
            def f(self, batch, aux):
                xf = batch.carry["x_final"]
                if xf.shape[0] > LANE:
                    if batch.requests[LANE] is not None:
                        REACHED.add(batch.requests[LANE].rid)
                    batch.carry = dict(batch.carry,
                                       x_final=xf.at[LANE].set(xf[LANE - 1]))
                return orig(self, batch, aux)
            return f
        undo = [_patch(ServeEngine, "_serve", solve_lane),
                _patch(ContinuousBatcher, "_harvest", step_lane)]
        return lambda: [u() for u in undo]
    raise ValueError(f"no fault {name!r}")
