"""Plain reference: the served sample, recomputed without the program.

Nothing here imports the program under test. It recomputes, for a
request, what the server is meant to return: SA-Solver (data
prediction, PEC, the configured orders and tau) on the VP-linear
schedule's uniform log-SNR grid, driving a classifier-free-guided DiT
written out in plain ``jax.numpy`` at float32 with every contraction at
``Precision.HIGHEST``. Its coefficient tables come from Gauss-Legendre
quadrature of the Lagrange basis in float64 (the program integrates the
same polynomials in closed form), and its noise follows the server's
stated convention: the initial latent from ``fold_in(key(noise_seed),
rid)``, the per-step noise from ``split(fold_in(key(solve_seed), rid),
n_steps)``.

``quant="fp8"`` is the control: the same computation with float8 (e4m3)
where the program computes in bfloat16 (see ``dit``), the step below the
bfloat16 compute the configuration states.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


# ------------------------------------------------------------ the solver
def vp_linear_lam(t, beta_0: float, beta_1: float):
    t = np.asarray(t, np.float64)
    log_alpha = -(t * t) * (beta_1 - beta_0) / 4.0 - t * beta_0 / 2.0
    return log_alpha - 0.5 * np.log(-np.expm1(2.0 * log_alpha))


def vp_linear_t(lam, beta_0: float, beta_1: float):
    lam = np.asarray(lam, np.float64)
    log_alpha = -0.5 * np.log1p(np.exp(-2.0 * lam))
    a, b = (beta_1 - beta_0) / 4.0, beta_0 / 2.0
    return (-b + np.sqrt(b * b - 4.0 * a * log_alpha)) / (2.0 * a)


def _lagrange(nodes: np.ndarray, j: int, x: np.ndarray) -> np.ndarray:
    out = np.ones_like(x)
    for m, v in enumerate(nodes):
        if m != j:
            out *= (x - v) / (nodes[j] - v)
    return out


def sa_tables(schedule: dict, solver: dict) -> dict:
    """Per-interval SA-Solver constants in float64 (paper Eqs. 14-18):
    state decay, noise std, predictor weights over the newest-first
    history, and the corrector's weights of the new and past
    evaluations. The weight of an evaluation is
    ``alpha_{i+1} (1 + tau^2) Int e^{(1 + tau^2)(lam - lam_{i+1})} l_j(lam)``
    over the interval, with ``l_j`` the Lagrange basis on its nodes."""
    b0, b1 = schedule["beta_0"], schedule["beta_1"]
    M = solver["n_steps"]
    t0, t1 = schedule["t_start"], schedule["t_end"]
    lams = np.linspace(vp_linear_lam(t0, b0, b1), vp_linear_lam(t1, b0, b1),
                       M + 1)
    ts = vp_linear_t(lams, b0, b1)
    ts[0], ts[-1] = t0, t1
    lams = vp_linear_lam(ts, b0, b1)
    alpha = np.sqrt(1.0 / (1.0 + np.exp(-2.0 * lams)))
    sigma = np.sqrt(1.0 / (1.0 + np.exp(2.0 * lams)))
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


# ---------------------------------------------------------- the backbone
def _fp8(x):
    """Rounding to float8 e4m3 with a per-tensor scale (amax to 448)."""
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _dense(x, w, r, contract: int = 1):
    """x [..., K] (or [..., K1, K2] with contract=2) times w [K..., N...],
    both operands through the rounding ``r``."""
    return jnp.tensordot(r(x), r(w), axes=contract, precision=HIGHEST)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + w)


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                     * (x + 0.044715 * x ** 3)))


def dit(params, z, t, model: dict, quant=None):
    """x0-prediction of the DiT backbone: z [B, S, dz], t scalar.
    ``quant="fp8"`` rounds, where the program keeps bfloat16, to float8:
    the input latent, the residual stream, the branch outputs and both
    operands of every dense projection."""
    r = _fp8 if quant == "fp8" else (lambda a: a)
    eps = model["norm_eps"]
    hd = model["head_dim"]
    dp = params["denoiser"]
    half = model["time_embed_dim"] // 2
    freqs = jnp.exp(-math.log(10000.0) * jnp.arange(half) / half)
    ang = jnp.asarray(t, jnp.float32) * freqs
    temb = jnp.concatenate([jnp.cos(ang), jnp.sin(ang)])
    tc = jnp.dot(jax.nn.silu(jnp.dot(temb, dp["t_mlp1"], precision=HIGHEST)),
                 dp["t_mlp2"], precision=HIGHEST)
    x = r(_dense(z, dp["in_proj"], r))

    def layer(x, p):
        mod = jnp.dot(tc, p["adaln"], precision=HIGHEST)
        s1, g1, b1, s2, g2, b2 = jnp.split(mod, 6)
        h = _rms(x, p["ln1"], eps) * (1.0 + s1) + b1
        q = _dense(h, p["attn"]["wq"], r)
        k = _dense(h, p["attn"]["wk"], r)
        v = _dense(h, p["attn"]["wv"], r)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HIGHEST) \
            / math.sqrt(hd)
        o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v,
                       precision=HIGHEST)
        x = r(x + g1 * r(_dense(o, p["attn"]["wo"], r, contract=2)))
        h = _rms(x, p["ln2"], eps) * (1.0 + s2) + b2
        m = _dense(_gelu_tanh(_dense(h, p["mlp"]["wi"], r)),
                   p["mlp"]["wo"], r)
        return r(x + g2 * r(m)), None

    x, _ = jax.lax.scan(layer, x, params["blocks"])
    return _dense(_rms(x, params["ln_f"], eps), dp["out_proj"], r)


# ---------------------------------------------------- the guided solve
@functools.partial(jax.jit, static_argnames=("model_items", "quant"))
def _solve(params, tables, x_T, xis, cond, scale, *, model_items, quant):
    """x_T [B,S,dz], xis [M,B,S,dz], cond [B,dz], scale [B] -> x0."""
    model = dict(model_items)
    g = scale[:, None, None]

    def guided(x, t):
        f = dit(params, jnp.concatenate([x + cond[:, None, :], x]), t,
                model, quant)
        f_c, f_u = jnp.split(f, 2)
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


def sample(params, model: dict, schedule: dict, solver: dict, *, rids,
           conds, scales, noise_seed: int, solve_seed: int, tokens: int,
           quant=None, chunk: int = 4) -> np.ndarray:
    """The reference sample of each request, ``chunk`` requests at a time
    so that its activations fit beside nothing else."""
    tables = {k: jnp.asarray(v, jnp.float32)
              for k, v in sa_tables(schedule, solver).items()}
    shape = (tokens, model["latent_dim"])
    items = tuple(sorted((k, v) for k, v in model.items()
                         if isinstance(v, (int, float, str))))
    out = []
    for lo in range(0, len(rids), chunk):
        r = jnp.asarray(rids[lo:lo + chunk], jnp.int32)
        x_T, xis = _noise(noise_seed, solve_seed, r, shape=shape,
                          n_steps=solver["n_steps"])
        out.append(np.asarray(_solve(
            params, tables, x_T, xis,
            jnp.asarray(np.stack(conds[lo:lo + chunk]), jnp.float32),
            jnp.asarray(scales[lo:lo + chunk], jnp.float32),
            model_items=items, quant=quant)))
    return np.concatenate(out)
