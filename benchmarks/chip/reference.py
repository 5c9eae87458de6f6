"""Plain reference: the served sample, recomputed without the program.

Nothing here imports the program under test. It recomputes, for a
request, what the server is meant to return: SA-Solver (data
prediction, PEC, the configured orders and tau) on the configured
schedule's uniform log-SNR grid (``schedules/<kind>.py``), driving the
configuration's family's backbone under classifier-free guidance
(``families/<family>.py``: ``reference_pair``, written out in plain
``jax.numpy`` at float32 with every contraction at
``Precision.HIGHEST``). Its coefficient tables come from Gauss-Legendre
quadrature of the Lagrange basis in float64 (the program integrates the
same polynomials in closed form), and its noise follows the server's
stated convention: the initial latent from ``fold_in(key(noise_seed),
rid)``, the per-step noise from ``split(fold_in(key(solve_seed), rid),
n_steps)``.

``quant="fp8"`` is the control: the same computation with float8 (e4m3)
where the program computes in bfloat16 (``rounding``, applied where the
family's backbone says), the step below the bfloat16 compute the
configuration states.
"""

from __future__ import annotations

import functools
import math
import os

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip import modules

HERE = os.path.dirname(os.path.abspath(__file__))
HIGHEST = jax.lax.Precision.HIGHEST


# ------------------------------------------------------------ the solver
def _lagrange(nodes: np.ndarray, j: int, x: np.ndarray) -> np.ndarray:
    out = np.ones_like(x)
    for m, v in enumerate(nodes):
        if m != j:
            out *= (x - v) / (nodes[j] - v)
    return out


def sa_tables(schedule: dict, solver: dict, data_dir: str = HERE) -> dict:
    """Per-interval SA-Solver constants in float64 (paper Eqs. 14-18):
    state decay, noise std, predictor weights over the newest-first
    history, and the corrector's weights of the new and past
    evaluations. The weight of an evaluation is
    ``alpha_{i+1} (1 + tau^2) Int e^{(1 + tau^2)(lam - lam_{i+1})} l_j(lam)``
    over the interval, with ``l_j`` the Lagrange basis on its nodes. The
    schedule's ``lam``, its inverse, ``alpha`` and ``sigma`` come from
    ``schedules/<schedule["kind"]>.py``; the grid is uniform in ``lam``
    with its end points pinned to ``t_start`` and ``t_end``."""
    sched = modules.load(data_dir, "schedules", schedule["kind"])
    M = solver["n_steps"]
    t0, t1 = schedule["t_start"], schedule["t_end"]
    lams = np.linspace(sched.lam(t0, schedule), sched.lam(t1, schedule),
                       M + 1)
    ts = sched.t_of_lam(lams, schedule)
    ts[0], ts[-1] = t0, t1
    lams = sched.lam(ts, schedule)
    alpha = sched.alpha(ts, schedule)
    sigma = sched.sigma(ts, schedule)
    tau2 = solver["tau"] ** 2
    a = 1.0 + tau2
    P = max(solver["predictor_order"], solver["corrector_order"], 1)
    gx, gw = np.polynomial.legendre.leggauss(24)
    decay, noise = np.zeros(M), np.zeros(M)
    pred, corr, corr_new = np.zeros((M, P)), np.zeros((M, P)), np.zeros(M)

    def weights(nodes, lo, hi, alpha_next):
        x = 0.5 * (hi - lo) * gx + 0.5 * (hi + lo)
        w = 0.5 * (hi - lo) * gw * a * np.exp(a * (x - hi))
        return np.array([alpha_next * np.sum(w * _lagrange(nodes, j, x))
                         for j in range(len(nodes))])

    for i in range(M):
        h = lams[i + 1] - lams[i]
        decay[i] = sigma[i + 1] / sigma[i] * math.exp(-tau2 * h)
        noise[i] = sigma[i + 1] * math.sqrt(-math.expm1(-2.0 * tau2 * h))
        p = min(i + 1, solver["predictor_order"])
        pred[i, :p] = weights(lams[i - np.arange(p)], lams[i], lams[i + 1],
                              alpha[i + 1])
        c = min(i + 1, solver["corrector_order"])
        if c > 0:
            row = weights(np.concatenate([[lams[i + 1]],
                                          lams[i - np.arange(c)]]),
                          lams[i], lams[i + 1], alpha[i + 1])
            corr_new[i], corr[i, :c] = row[0], row[1:]
    return {"ts": ts, "decay": decay, "noise": noise, "pred": pred,
            "corr_new": corr_new, "corr": corr}


# ------------------------------------------------- the control's rounding
def _fp8(x):
    """Rounding to float8 e4m3 with a per-tensor scale (amax to 448)."""
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def rounding(quant):
    """The rounding a family's backbone applies where the program keeps
    bfloat16: float8 under the control, none otherwise."""
    return _fp8 if quant == "fp8" else (lambda a: a)


def dense(x, w, r, contract: int = 1):
    """x [..., K] (or [..., K1, K2] with contract=2) times w [K..., N...],
    both operands through the rounding ``r``."""
    return jnp.tensordot(r(x), r(w), axes=contract, precision=HIGHEST)


# ---------------------------------------------------- the guided solve
@functools.partial(jax.jit,
                   static_argnames=("pair", "model_items", "quant"))
def _solve(params, tables, x_T, xis, cond, scale, *, pair, model_items,
           quant):
    """x_T [B,S,dz], xis [M,B,S,dz], cond (each leaf [B,...]), scale [B]
    -> x0; ``pair`` is the family's ``reference_pair``."""
    model = dict(model_items)
    g = scale[:, None, None]

    def guided(x, t):
        f_c, f_u = pair(params, x, t, cond, model, quant)
        return (1.0 - g) * f_u + g * f_c

    P = tables["pred"].shape[1]
    hist = jnp.zeros((P,) + x_T.shape, jnp.float32).at[0].set(
        guided(x_T, tables["ts"][0]))

    def step(carry, per):
        x, hist = carry
        decay, noise, pred, corr_new, corr, t_next, xi = per
        base = decay * x + noise * xi
        x_pred = base + jnp.tensordot(pred, hist, 1)
        d_new = guided(x_pred, t_next)
        x_next = base + corr_new * d_new + jnp.tensordot(corr, hist, 1)
        hist = jnp.concatenate([d_new[None], hist[:-1]])
        return (x_next, hist), None

    per = (tables["decay"], tables["noise"], tables["pred"],
           tables["corr_new"], tables["corr"], tables["ts"][1:], xis)
    (_, hist), _ = jax.lax.scan(step, (x_T, hist), per)
    return hist[0]


@functools.partial(jax.jit, static_argnames=("shape", "n_steps"))
def _noise(noise_seed, solve_seed, rids, *, shape, n_steps):
    def one(rid):
        x_T = jax.random.normal(
            jax.random.fold_in(jax.random.PRNGKey(noise_seed), rid), shape)
        keys = jax.random.split(
            jax.random.fold_in(jax.random.PRNGKey(solve_seed), rid), n_steps)
        return x_T, jax.vmap(lambda k: jax.random.normal(k, shape))(keys)
    x_T, xis = jax.vmap(one)(rids)
    return x_T, jnp.swapaxes(xis, 0, 1)


def sample(params, model: dict, schedule: dict, solver: dict, *, family,
           rids, conds, scales, noise_seed: int, solve_seed: int,
           tokens: int, quant=None, chunk: int = 4,
           data_dir: str = HERE) -> np.ndarray:
    """The reference sample of each request, ``chunk`` requests at a time
    so that its activations fit beside nothing else. ``family`` is the
    configuration's family module; ``data_dir`` holds its schedule's."""
    tables = {k: jnp.asarray(v, jnp.float32)
              for k, v in sa_tables(schedule, solver, data_dir).items()}
    shape = (tokens, model["latent_dim"])
    items = tuple(sorted((k, v) for k, v in model.items()
                         if isinstance(v, (int, float, str))))
    out = []
    for lo in range(0, len(rids), chunk):
        r = jnp.asarray(rids[lo:lo + chunk], jnp.int32)
        x_T, xis = _noise(noise_seed, solve_seed, r, shape=shape,
                          n_steps=solver["n_steps"])
        cond = jax.tree.map(lambda *c: jnp.asarray(np.stack(c)),
                            *conds[lo:lo + chunk])
        out.append(np.asarray(_solve(
            params, tables, x_T, xis, cond,
            jnp.asarray(scales[lo:lo + chunk], jnp.float32),
            pair=family.reference_pair, model_items=items, quant=quant)))
    return np.concatenate(out)
