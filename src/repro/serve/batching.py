"""Plan-keyed continuous microbatching for the diffusion serve engine.

Requests are grouped by **bucket key** — ``(SamplerSpec, latent shape,
dtype, cond structure)`` — because that tuple determines the compiled
executor: the spec
fixes the sampler family and its trace-relevant statics (including the
denoiser adapter's prediction type, the guidance on/off flag, the
history layout, the ``precision`` policy — an f32 and a bf16
request compile different hot loops and therefore land in different
buckets — and the step ``program``, whose mode pattern shapes the
traced scan segments), the
shape/dtype fix the argument avals, and the conditioning pytree joins
only by its shape/dtype *structure*. Everything else (tau value,
per-interval program orders/taus, coefficient tables, the solve grid
values, the conditioning values, the
guidance scale) is traced data, so requests that differ only in
those ride the same executable — a guidance-scale sweep never recompiles.

Within a bucket-key group, requests are chunked FIFO into microbatches of
at most ``max(bucket_sizes)``; a ragged tail takes the *smallest*
configured bucket that fits it and is padded with masked dummy slots
(``PAD_RID``) — never by duplicating a real request, which would re-solve
it and corrupt throughput accounting. Padded lanes are computed (static
batch shapes are what make the compile cache work) but their outputs are
dropped when results are scattered back to requests.

Per-request RNG is derived purely from the request id —
``fold_in(base, rid)`` — so a request's noise draw and solve path are
independent of which microbatch it lands in. Within one bucket *size*
(one executable) re-bucketing — different arrival order, neighbours, or
pad count — cannot change a request's bytes (vmap lanes are independent);
across different bucket sizes the executables differ and results agree
only to float-reassociation level (~1e-5 relative).
"""

from __future__ import annotations

import dataclasses
from collections import OrderedDict
from typing import Any, Sequence

import jax
import jax.numpy as jnp

from ..core.samplers import SamplerSpec, cond_struct

__all__ = [
    "PAD_RID",
    "Request",
    "MicroBatch",
    "ServeResult",
    "bucket_key",
    "bucket_label",
    "choose_bucket",
    "cond_struct",
    "form_microbatches",
    "fold_keys",
    "retry_fold",
]

#: rid assigned to padded lanes; int32-max so it cannot collide with real
#: engine-assigned ids (which count up from 0)
PAD_RID = 2**31 - 1


@dataclasses.dataclass(frozen=True)
class Request:
    """One sampling request: which sampler configuration, what latent,
    and — for Denoiser-backed engines — its conditioning pytree and
    guidance scale. ``cond`` and ``guidance_scale`` are *data*: they ride
    the executor as traced arguments and never force a recompile (only
    cond's shape/dtype structure enters the bucket key)."""

    rid: int
    spec: SamplerSpec
    shape: tuple[int, ...]
    dtype: str = "float32"
    cond: Any = None
    guidance_scale: float = 1.0
    # -- scheduling metadata (step-granular scheduler; NOT in the bucket
    # key — none of it is trace-relevant, so it can never split a bucket
    # or recompile) --
    #: higher runs first (ties broken by deadline, then arrival)
    priority: int = 0
    #: absolute ``time.monotonic()`` deadline; pending requests past it
    #: are shed with ``status="shed"`` instead of joining a batch
    deadline: float | None = None
    #: masked early-exit tolerance on the per-step predictor-vs-corrector
    #: residual; <= 0 disables (the disabled path is the solver's exact
    #: whole-solve trajectory)
    early_exit_tol: float = 0.0
    #: steps a lane must complete before early exit may fire; None
    #: defaults to the spec's solver order (the multistep warm-up, where
    #: the residual is not yet meaningful)
    min_steps: int | None = None
    # -- retry bookkeeping (set by the engine when a failed request is
    # re-enqueued; also not trace-relevant) --
    #: 0 for the original submission, incremented per retry; folds into
    #: the RNG streams (attempt 0 is bitwise the base stream)
    attempt: int = 0
    #: ``time.monotonic()`` before which the retry must not be served
    #: (exponential backoff after host-side faults; 0 = immediately)
    not_before: float = 0.0
    #: label of the degradation-ladder rung this retry runs at (a tier
    #: name or "tau0"); None while undegraded
    degraded_to: str | None = None
    #: ``time.monotonic()`` of the request's first enqueue (set by the
    #: step scheduler's ``enqueue``; a retry keeps it), so a join can
    #: report how long the request queued
    enqueued: float | None = None


@dataclasses.dataclass(frozen=True)
class ServeResult:
    """One served request: final latent plus optional streamed previews."""

    rid: int
    x0: jnp.ndarray | None
    #: ``[n_steps, *shape]`` per-step denoised previews (stream=True
    #: only), in per-request step order — under the step scheduler an
    #: early-exited lane carries fewer rows than the full solve
    previews: jnp.ndarray | None = None
    #: terminal status — x0 is None for everything but "ok":
    #: - "ok": served (possibly on a degraded retry; see degraded_to)
    #: - "shed": deadline expired before the request got a lane (step
    #:   scheduler only)
    #: - "failed_numerics": the per-lane numerical guard tripped
    #:   (non-finite state) and retries were exhausted
    #: - "failed": a host-side fault (model exception, injected failure)
    #:   outlived the retry budget
    status: str = "ok"
    #: solver steps actually run (step scheduler; None under "solve",
    #: where every request runs its spec's full step count)
    n_steps: int | None = None
    #: serve attempts consumed (1 = first try succeeded; retries add 1
    #: each, so a result that failed after 2 retries reports 3)
    attempts: int = 1
    #: degradation-ladder rung the final attempt ran at (a tier name,
    #: "tau0", or "spec:name/steps"); None when served undegraded
    degraded_to: str | None = None
    #: last error string for failed results; None on success
    error: str | None = None


def bucket_key(req: Request) -> tuple:
    """The executor identity this request compiles under."""
    return (req.spec, req.shape, req.dtype, cond_struct(req.cond))


def bucket_label(key: tuple) -> str:
    """Human-readable stats key for one bucket: family/steps/shape/dtype.

    Coarser than the bucket key on purpose (tau, program data, cond
    values don't change the compiled work per lane-step) — stats
    aggregate across them.
    """
    spec, shape, dtype = key[0], key[1], key[2]
    return (f"{spec.name}/{spec.n_steps}step/"
            f"{'x'.join(str(s) for s in shape)}/{dtype}")


@dataclasses.dataclass(frozen=True)
class MicroBatch:
    """A bucket's worth of work: ``size`` lanes, ``requests`` real ones."""

    key: tuple
    requests: tuple[Request, ...]
    size: int  # padded lane count (a configured bucket size)

    @property
    def n_padded(self) -> int:
        return self.size - len(self.requests)

    @property
    def spec(self) -> SamplerSpec:
        return self.key[0]

    @property
    def shape(self) -> tuple[int, ...]:
        return self.key[1]

    @property
    def dtype(self) -> str:
        return self.key[2]

    def rids(self) -> list[int]:
        """Lane rids including pad slots."""
        return [r.rid for r in self.requests] \
            + [PAD_RID] * (self.size - len(self.requests))

    def stacked_cond(self):
        """Per-lane conditioning: real requests' cond pytrees stacked
        along a new leading lane axis, pad lanes as zeros (the null
        conditioning; their outputs are dropped anyway). None when this
        bucket is unconditional."""
        c0 = self.requests[0].cond
        if c0 is None:
            return None
        conds = [r.cond for r in self.requests]
        conds += [jax.tree.map(jnp.zeros_like, c0)] * self.n_padded
        return jax.tree.map(lambda *ls: jnp.stack(ls), *conds)

    def scales(self) -> jnp.ndarray:
        """Per-lane guidance scales ``[size]`` (pad lanes at 1.0)."""
        return jnp.asarray(
            [float(r.guidance_scale) for r in self.requests]
            + [1.0] * self.n_padded, jnp.float32)


def choose_bucket(n: int, bucket_sizes: Sequence[int]) -> int:
    """Smallest configured bucket that fits ``n`` lanes (the largest
    bucket if none does — callers chunk to ``max(bucket_sizes)`` first)."""
    if n < 1:
        raise ValueError("empty microbatch")
    for b in sorted(bucket_sizes):
        if b >= n:
            return b
    return max(bucket_sizes)


def form_microbatches(requests: Sequence[Request],
                      bucket_sizes: Sequence[int]) -> list[MicroBatch]:
    """Group FIFO by bucket key, chunk to the largest bucket, size tails.

    Returns microbatches in first-arrival order of their bucket key, so a
    drain loop serves oldest work first.
    """
    if not bucket_sizes:
        raise ValueError("need at least one bucket size")
    cap = max(bucket_sizes)
    groups: OrderedDict[tuple, list[Request]] = OrderedDict()
    for r in requests:
        groups.setdefault(bucket_key(r), []).append(r)
    out = []
    for key, group in groups.items():
        for i in range(0, len(group), cap):
            chunk = tuple(group[i:i + cap])
            out.append(MicroBatch(key=key, requests=chunk,
                                  size=choose_bucket(len(chunk),
                                                     bucket_sizes)))
    return out


def fold_keys(base_key: jax.Array, rids) -> jax.Array:
    """``[n, 2]`` per-lane PRNG keys: ``fold_in(base, rid)`` per lane.

    Pure in the rid — the same rid always yields the same key, whatever
    bucket (or pad position) it is served in.
    """
    rids = jnp.asarray(rids, dtype=jnp.int32)
    return jax.vmap(lambda r: jax.random.fold_in(base_key, r))(rids)


def retry_fold(keys: jax.Array, attempts) -> jax.Array:
    """Fresh per-attempt subkeys: ``fold_in(key, attempt)`` per lane.

    A retried request must not replay the stream that just went
    non-finite, so each attempt folds its count into the rid-derived
    key. Attempt 0 is bitwise the base stream (``where`` selects the
    unfolded key), preserving every fault-free RNG contract.
    """
    a = jnp.asarray(attempts, dtype=jnp.int32)
    folded = jax.vmap(jax.random.fold_in)(keys, a)
    return jnp.where((a > 0)[:, None], folded, keys)
