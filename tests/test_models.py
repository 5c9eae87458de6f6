"""Per-architecture smoke tests: reduced configs, one forward + one train
step on CPU, asserting output shapes and no NaNs (assignment requirement),
plus prefill/decode consistency and denoiser-mode checks."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, get_meta, get_smoke
from repro.models import build_model, init_params

LM_ARCHS = [a for a in ARCHS if get_meta(a).family != "denoiser"]


def make_batch(cfg, key, B=2, S=32):
    if getattr(cfg, "input_mode", "tokens") == "embeds":
        batch = {"embeds": jax.random.normal(key, (B, S, cfg.d_model)),
                 "labels": jnp.ones((B, S), jnp.int32)}
    else:
        batch = {"tokens": jax.random.randint(key, (B, S), 0, cfg.vocab_size),
                 "labels": jnp.ones((B, S), jnp.int32)}
    if getattr(cfg, "rope_type", "") == "mrope":
        batch["positions"] = jnp.broadcast_to(jnp.arange(S)[None, None],
                                              (3, B, S))
    if getattr(cfg, "mtp", False):
        batch["labels2"] = batch["labels"]
    return batch


@pytest.mark.slow
@pytest.mark.parametrize("arch", LM_ARCHS)
def test_smoke_forward_and_train_step(arch):
    cfg = get_smoke(arch)
    model = build_model(cfg)
    params = init_params(jax.random.PRNGKey(0), model.param_defs(), jnp.float32)
    batch = make_batch(cfg, jax.random.PRNGKey(1))

    logits, aux = model.forward(params, batch)
    assert logits.shape == (2, 32, cfg.vocab_size)
    assert bool(jnp.all(jnp.isfinite(logits))), arch

    loss, grads = jax.value_and_grad(model.loss_fn)(params, batch)
    assert bool(jnp.isfinite(loss)), arch
    gn = sum(float(jnp.sum(jnp.abs(g))) for g in jax.tree.leaves(grads))
    assert np.isfinite(gn) and gn > 0, arch
    # one SGD step moves the loss
    params2 = jax.tree.map(lambda p, g: p - 1e-2 * g, params, grads)
    loss2 = model.loss_fn(params2, batch)
    assert bool(jnp.isfinite(loss2))


@pytest.mark.slow
@pytest.mark.parametrize("arch", LM_ARCHS)
def test_prefill_matches_forward(arch):
    cfg = get_smoke(arch)
    cfg = dataclasses.replace(cfg, dtype=jnp.float32)  # exactness test
    if hasattr(cfg, "cache_dtype"):
        cfg = dataclasses.replace(cfg, cache_dtype=jnp.float32)
    model = build_model(cfg)
    params = init_params(jax.random.PRNGKey(0), model.param_defs(), jnp.float32)
    batch = make_batch(cfg, jax.random.PRNGKey(1))
    fw, _ = model.forward(params, batch)
    cache = model.init_cache(2, 48)
    lg, cache = model.prefill(params, batch, cache)
    np.testing.assert_allclose(np.asarray(fw[:, -1:]), np.asarray(lg),
                               rtol=2e-3, atol=2e-3)
    # decode one more token; logits stay finite and shaped
    if getattr(cfg, "input_mode", "tokens") == "embeds":
        tok = jnp.zeros((2, 1, cfg.d_model))
    else:
        tok = jnp.zeros((2, 1), jnp.int32)
    lg2, cache = model.decode_step(params, tok, cache, 32)
    assert lg2.shape == (2, 1, cfg.vocab_size)
    assert bool(jnp.all(jnp.isfinite(lg2)))


@pytest.mark.slow
@pytest.mark.parametrize("arch", LM_ARCHS)
def test_decode_matches_forward_token_by_token(arch):
    """Greedy decode equivalence: running the full sequence through
    forward() must produce the same last-position logits as prefill(k) +
    decode_step x (S-k)."""
    cfg = get_smoke(arch)
    cfg = dataclasses.replace(cfg, dtype=jnp.float32)  # exactness test
    if hasattr(cfg, "cache_dtype"):
        cfg = dataclasses.replace(cfg, cache_dtype=jnp.float32)
    if getattr(cfg, "moe", None) is not None:
        # capacity-based routing drops tokens in full-sequence forward but
        # not in per-token decode (C=1 covers every step) — a well-known
        # train/serve inconsistency of capacity MoE. Make the test
        # drop-free so it checks the cache math, not the drop policy.
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=8.0))
    model = build_model(cfg)
    params = init_params(jax.random.PRNGKey(0), model.param_defs(), jnp.float32)
    B, S, k = 2, 16, 12
    full = make_batch(cfg, jax.random.PRNGKey(1), B=B, S=S)
    fw, _ = model.forward(params, full)

    def sub(b, lo, hi):
        out = {}
        for kk, v in b.items():
            if kk == "positions":
                out[kk] = v[:, :, lo:hi]
            elif v.ndim >= 2 and v.shape[1] == S:
                out[kk] = v[:, lo:hi]
        return out

    cache = model.init_cache(B, S)
    _, cache = model.prefill(params, sub(full, 0, k), cache)
    for i in range(k, S):
        step = sub(full, i, i + 1)
        tok = step.get("tokens", step.get("embeds"))
        lg, cache = model.decode_step(params, tok, cache, i)
    np.testing.assert_allclose(np.asarray(fw[:, -1]), np.asarray(lg[:, -1]),
                               rtol=3e-3, atol=3e-3)


@pytest.mark.slow
@pytest.mark.parametrize("arch", ["dit-s", "rwkv6-3b", "zamba2-7b",
                                  "starcoder2-3b"])
def test_denoiser_mode(arch):
    cfg = get_smoke(arch)
    if getattr(cfg, "denoiser_latent", None) is None:
        cfg = dataclasses.replace(cfg, denoiser_latent=8)
    model = build_model(cfg)
    params = init_params(jax.random.PRNGKey(0), model.param_defs(), jnp.float32)
    # adaLN-zero / zero-out-proj init produces exactly-zero outputs by
    # design; randomize the zero-initialized heads so conditioning is
    # observable
    def derandomize(tree, key=[0]):
        def f(v):
            key[0] += 1
            return v + 0.02 * jax.random.normal(jax.random.PRNGKey(key[0]),
                                                v.shape, v.dtype)
        return jax.tree.map(f, tree)
    params["denoiser"] = derandomize(params["denoiser"])
    for blk in ("blocks", "moe_blocks"):
        if isinstance(params, dict) and blk in params and \
                isinstance(params[blk], dict) and "adaln" in params[blk]:
            params[blk]["adaln"] = derandomize(params[blk]["adaln"])
    z = jax.random.normal(jax.random.PRNGKey(2), (2, 32, cfg.denoiser_latent))
    out = model.denoise(params, z, 0.5)
    assert out.shape == z.shape
    assert bool(jnp.all(jnp.isfinite(out)))
    # time conditioning is live: different t => different output
    out2 = model.denoise(params, z, 0.9)
    assert float(jnp.max(jnp.abs(out - out2))) > 0


def test_denoiser_tcond_stays_f32_under_bf16():
    """Precision-policy regression (non-slow: tier-1 guard). Under a
    bf16 model dtype the timestep/conditioning path must stay f32: bf16
    has 8 mantissa bits, so adjacent solver timesteps would collapse to
    one embedding and bias the whole trajectory. Two timesteps closer
    than a bf16 ulp must still produce distinct adaLN signals — and
    distinct denoise outputs."""
    cfg = dataclasses.replace(get_smoke("dit-s"), dtype=jnp.bfloat16)
    model = build_model(cfg)
    params = init_params(jax.random.PRNGKey(0), model.param_defs(),
                         jnp.float32)
    # adaLN-zero init would make the output t-independent; perturb
    params = jax.tree.map(
        lambda p: p + 0.02 * jax.random.normal(jax.random.PRNGKey(1),
                                               p.shape, p.dtype), params)
    t1 = 0.5
    t2 = 0.5 * (1 + 2 ** -9)  # < half a bf16 ulp away from t1
    assert jnp.bfloat16(t1) == jnp.bfloat16(t2)
    tc1 = model._tcond(params["denoiser"], t1, 2, None)
    tc2 = model._tcond(params["denoiser"], t2, 2, None)
    assert tc1.dtype == jnp.float32
    assert float(jnp.max(jnp.abs(tc1 - tc2))) > 0, \
        "timestep embedding quantized: sub-bf16-ulp timesteps collapsed"
    z = jax.random.normal(jax.random.PRNGKey(2),
                          (2, 32, cfg.denoiser_latent))
    o1 = model.denoise(params, z, t1)
    o2 = model.denoise(params, z, t2)
    assert float(jnp.max(jnp.abs(o1 - o2))) > 0


def test_param_counts_match_published():
    from repro.configs import get_config
    expect = {
        "granite-34b": 34e9, "starcoder2-15b": 16e9, "starcoder2-3b": 3.2e9,
        "gemma-7b": 8.5e9, "rwkv6-3b": 2.9e9, "qwen2-vl-2b": 1.5e9,
        "deepseek-v3-671b": 671e9, "dbrx-132b": 132e9, "zamba2-7b": 7.1e9,
    }
    for arch, want in expect.items():
        total, _ = get_config(arch).param_count()
        assert abs(total - want) / want < 0.12, (arch, total, want)
    # deepseek active ~37B
    _, active = get_config("deepseek-v3-671b").param_count()
    assert abs(active - 37e9) / 37e9 < 0.1


@pytest.mark.parametrize("backend,q_len,kv_len,cached,softcap,causal,want", [
    ("cpu", 1024, 1024, False, None, False, False),  # off the chip: jnp
    ("tpu", 1024, 1024, True, None, False, False),   # KV cache (LM decode)
    ("tpu", 1024, 1024, False, 50.0, False, False),  # logit softcap
    ("tpu", 256, 256, False, None, False, False),    # DiT at 256 px: jnp wins
    ("tpu", 1024, 1024, False, None, True, False),   # causal: not measured
    ("tpu", 1024, 1024, False, None, False, True),   # DiT-XL/2 at 512 px
], ids=["cpu", "cached", "softcap", "dit-256", "causal", "dit-1024"])
def test_attention_kernel_dispatch(backend, q_len, kv_len, cached, softcap,
                                   causal, want):
    from repro.models.attention import use_kernel
    assert use_kernel(backend, q_len, kv_len, cached, softcap,
                      causal) is want


def _qkv(shape=(2, 256, 4, 72)):
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    return tuple(jax.random.normal(kk, shape) for kk in ks)


@pytest.mark.parametrize("causal", [False, True])
def test_sdpa_kernel_path_matches_jnp_path(monkeypatch, causal):
    """``_sdpa``'s kernel branch (layout changes, bf16 operands, the
    kernel in interpret mode) against its jnp branch, [B,S,H,hd] in and
    out, within bfloat16 rounding, eager and under jit and vmap; and with
    the output projection, which each branch writes in its own form."""
    from repro.models import attention
    q, k, v = _qkv()
    wo = jax.random.normal(jax.random.PRNGKey(6), (4, 72, 32)) / 17.0
    want = attention._sdpa(q, k, v, causal=causal)
    want_y = attention._sdpa(q, k, v, causal=causal, wo=wo)
    monkeypatch.setattr(attention, "use_kernel", lambda *a: True)
    sdpa = lambda q, k, v: attention._sdpa(q, k, v, causal=causal)
    two = lambda a: jnp.stack([a, a], axis=1)  # two lanes, k shared
    lanes = jax.jit(jax.vmap(sdpa, in_axes=(1, None, 1)))(two(q), k, two(v))
    for got in (sdpa(q, k, v), jax.jit(sdpa)(q, k, v), lanes[0], lanes[1]):
        assert got.shape == want.shape and got.dtype == want.dtype
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=4e-2, rtol=4e-2)
    got_y = attention._sdpa(q, k, v, causal=causal, wo=wo)
    assert got_y.shape == want_y.shape == (2, 256, 32)
    np.testing.assert_allclose(np.asarray(got_y), np.asarray(want_y),
                               atol=4e-2, rtol=4e-2)


@pytest.mark.parametrize("mode", ["grad", "jvp"])
def test_sdpa_kernel_path_differentiates_as_jnp_path(monkeypatch, mode):
    """The kernel branch has the jnp branch's derivatives (training takes
    ``jax.grad`` through the DiT's attention, the tests ``jax.jvp``)."""
    from repro.models import attention
    q, k, v = _qkv((1, 64, 2, 16))
    w = jax.random.normal(jax.random.PRNGKey(5), q.shape)
    loss = lambda q, k, v: jnp.sum(w * attention._sdpa(q, k, v,
                                                      causal=False))

    def derivs():
        if mode == "grad":
            return jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)
        return jax.jvp(loss, (q, k, v), (w, -w, 2 * w))[1:]

    want = derivs()
    monkeypatch.setattr(attention, "use_kernel", lambda *a: True)
    for g, h in zip(derivs(), want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(h),
                                   atol=1e-5, rtol=1e-5)
