"""Pallas kernels vs pure-jnp oracles — shape/dtype sweeps, interpret mode."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import flash_attention
from repro.kernels.ref import (flash_attention_ref, sa_fused_update_ref,
                               sa_update_ref, wkv_ref)
from repro.kernels.rwkv6_scan import rwkv6_wkv
from repro.kernels.sa_fused import sa_fused_update
from repro.kernels.sa_update import LANE_ALIGN, choose_tile, sa_update


def attention_ref(q, k, v, *, causal):
    """``flash_attention_ref`` ([B,H,S,hd]) in the kernel's layout
    ([B,S,H,hd])."""
    t = lambda a: jnp.swapaxes(a, 1, 2)
    return t(flash_attention_ref(t(q), t(k), t(v), causal=causal))


@pytest.mark.parametrize("shape", [(64,), (4, 100, 7), (2, 33, 5, 3), (1,)])
@pytest.mark.parametrize("P", [1, 3, 5])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_sa_update_sweep(shape, P, dtype):
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 3)
    x = jax.random.normal(ks[0], shape, dtype)
    buf = jax.random.normal(ks[1], (P,) + shape, dtype)
    xi = jax.random.normal(ks[2], shape, dtype)
    coeffs = jnp.asarray([0.9, 0.1] + [0.3 / (j + 1) for j in range(P)],
                         jnp.float32)
    out = sa_update(x, buf, xi, coeffs, tile=128)
    ref = sa_update_ref(x, buf, xi, coeffs)
    tol = 1e-6 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol, rtol=tol)


@pytest.mark.parametrize("shape", [(64,), (4, 100, 7), (1,)])
@pytest.mark.parametrize("P", [1, 3])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_sa_fused_sweep(shape, P, dtype):
    """Dual-output kernel vs its jnp oracle: both outputs, ragged tiles
    included ((4,100,7) has no 128-aligned divisor)."""
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    x = jax.random.normal(ks[0], shape, dtype)
    buf = jax.random.normal(ks[1], (P,) + shape, dtype)
    xi = jax.random.normal(ks[2], shape, dtype)
    coeffs = jnp.stack([
        jnp.asarray([0.9, 0.1] + [0.3 / (j + 1) for j in range(P)]),
        jnp.asarray([0.9, 0.1] + [-0.2 * (j + 1) for j in range(P)]),
    ]).astype(jnp.float32)
    pred, corr = sa_fused_update(x, buf, xi, coeffs, tile=128)
    pred_r, corr_r = sa_fused_update_ref(x, buf, xi, coeffs)
    tol = 2e-6 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(pred, np.float32),
                               np.asarray(pred_r, np.float32),
                               atol=tol, rtol=tol)
    np.testing.assert_allclose(np.asarray(corr, np.float32),
                               np.asarray(corr_r, np.float32),
                               atol=tol, rtol=tol)


def test_sa_fused_rows_match_single_combines():
    """Each fused output equals the single-combine oracle with the same
    packed row — the dual kernel is two sa_updates in one pass."""
    ks = jax.random.split(jax.random.PRNGKey(8), 3)
    x = jax.random.normal(ks[0], (512,))
    buf = jax.random.normal(ks[1], (3, 512))
    xi = jax.random.normal(ks[2], (512,))
    c = jnp.asarray([[0.8, 0.2, 0.1, -0.2, 0.3],
                     [0.8, 0.2, 0.4, 0.1, -0.1]], jnp.float32)
    pred, corr = sa_fused_update(x, buf, xi, c, tile=128)
    np.testing.assert_allclose(np.asarray(pred),
                               np.asarray(sa_update_ref(x, buf, xi, c[0])),
                               atol=2e-6, rtol=2e-6)
    np.testing.assert_allclose(np.asarray(corr),
                               np.asarray(sa_update_ref(x, buf, xi, c[1])),
                               atol=2e-6, rtol=2e-6)


def test_choose_tile_prefers_aligned_divisors():
    """Steady-state scan steps must be copy-free: when the flattened size
    has a lane-aligned divisor, the tile divides it exactly (no padding,
    no ragged block); otherwise the requested tile is kept and the final
    block is masked."""
    A = LANE_ALIGN
    assert choose_tile(8 * A, 64 * A) == 8 * A          # n <= tile: one block
    assert choose_tile(6 * A, 4 * A) == 3 * A           # largest divisor <= 4A
    assert choose_tile(12 * A, 5 * A) == 4 * A
    assert 2800 % choose_tile(2800, 65536) == 0         # n itself
    assert choose_tile(2800, 128) == 128                # ragged fallback
    assert choose_tile(7, 128) == 7                     # tiny latent
    n = 100 * A + 3  # prime-ish: no aligned divisor
    assert choose_tile(n, 4 * A) == 4 * A
    # a tiny sole divisor (A * large_prime) must NOT shrink the tile to
    # A and explode the grid — the ragged masked path wins below tile/8
    assert choose_tile(A * 9973, 32 * A) == 32 * A


@pytest.mark.parametrize("S,dz", [(1500, 64), (1503, 8), (750, 128),
                                  (2048, 50)])
def test_choose_tile_long_seq_shapes(S, dz):
    """Musicgen-style long-sequence latents ((frames, codebook_dim),
    frames ~ O(1500), non-square): choose_tile must stay within the
    requested budget, and either divide the flattened size exactly
    (copy-free steady state) or keep the requested tile for the masked
    ragged path — never shrink below tile/8 chasing a tiny divisor."""
    n = S * dz
    for tile in (256, 1024, 8192):
        t = choose_tile(n, tile)
        assert t <= tile and t >= 1
        if n % t:  # ragged fallback keeps the request
            assert t == min(tile, n)
        elif t % LANE_ALIGN == 0:
            assert t >= tile // 8  # grid stays bounded


def test_sa_update_long_seq_exact():
    """The ring combine stays exact on a flattened non-square long-seq
    latent whose size has no tile-aligned divisor."""
    S, dz = 1500, 8  # 12000 = 2^5 * 3 * 5^3 -> no 256-aligned divisor
    ks = jax.random.split(jax.random.PRNGKey(11), 3)
    x = jax.random.normal(ks[0], (S, dz))
    buf = jax.random.normal(ks[1], (3, S, dz))
    xi = jax.random.normal(ks[2], (S, dz))
    c = jnp.asarray([0.8, 0.2, 0.3, -0.1, 0.05], jnp.float32)
    np.testing.assert_allclose(
        np.asarray(sa_update(x, buf, xi, c, tile=256)),
        np.asarray(sa_update_ref(x, buf, xi, c)), atol=1e-6, rtol=1e-6)


def test_sa_update_unaligned_sizes_are_exact():
    """Ragged final blocks (masked, not padded) stay exact for sizes with
    no aligned divisor."""
    for n in (1, 7, 130, 2800, 5003):
        ks = jax.random.split(jax.random.PRNGKey(n), 3)
        x = jax.random.normal(ks[0], (n,))
        buf = jax.random.normal(ks[1], (2, n))
        xi = jax.random.normal(ks[2], (n,))
        c = jnp.asarray([0.7, 0.1, 0.5, -0.3], jnp.float32)
        np.testing.assert_allclose(
            np.asarray(sa_update(x, buf, xi, c, tile=256)),
            np.asarray(sa_update_ref(x, buf, xi, c)), atol=1e-6, rtol=1e-6)


@pytest.mark.slow
@pytest.mark.parametrize("B,H,K,S,hd,bq,bk", [
    (2, 4, 4, 128, 64, 32, 32),    # MHA
    (1, 8, 2, 256, 32, 64, 64),    # GQA 4:1
    (2, 4, 1, 64, 16, 16, 16),     # MQA
    (1, 2, 2, 128, 128, 64, 32),   # bq != bk
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(B, H, K, S, hd, bq, bk, dtype):
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (B, S, H, hd), dtype)
    k = jax.random.normal(ks[1], (B, S, K, hd), dtype)
    v = jax.random.normal(ks[2], (B, S, K, hd), dtype)
    out = flash_attention(q, k, v, causal=True, bq=bq, bk=bk)
    ref = attention_ref(q, k, v, causal=True)
    tol = 2e-5 if dtype == jnp.float32 else 4e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol, rtol=tol)


@pytest.mark.parametrize("S", [19, 24, 33])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_ragged_lengths(S, causal, dtype):
    """Tier-1 guard for the fused e2e path: sequence lengths that are NOT
    block multiples (masked final q/k blocks) must match the reference at
    f32 and bf16. Small shapes so the sweep stays in the fast suite."""
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(ks[0], (1, S, 2, 16), dtype)
    k = jax.random.normal(ks[1], (1, S, 2, 16), dtype)
    v = jax.random.normal(ks[2], (1, S, 2, 16), dtype)
    out = flash_attention(q, k, v, causal=causal, bq=16, bk=16)
    ref = attention_ref(q, k, v, causal=causal)
    tol = 2e-5 if dtype == jnp.float32 else 4e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol, rtol=tol)


def test_flash_attention_noncausal():
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(ks[0], (1, 64, 2, 32))
    k = jax.random.normal(ks[1], (1, 64, 2, 32))
    v = jax.random.normal(ks[2], (1, 64, 2, 32))
    out = flash_attention(q, k, v, causal=False, bq=32, bk=32)
    ref = attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("S,bq,bk", [
    (256, None, None),    # the shapes' own blocks: bk == T
    (256, 128, 128),      # bk < T: online softmax over two KV blocks
    (1024, None, None),   # bk == T
    (1024, 256, 512),     # bk < T
], ids=["256-one-kv-block", "256-two-kv-blocks", "1024-one-kv-block",
        "1024-two-kv-blocks"])
def test_flash_attention_dit_head_dim_bf16(S, bq, bk):
    """The denoiser's case: non-causal, head_dim 72, bfloat16 operands
    (f32 accumulation and softmax), at the DiT-XL/2 token counts."""
    ks = jax.random.split(jax.random.PRNGKey(6), 3)
    q, k, v = (jax.random.normal(kk, (1, S, 4, 72), jnp.bfloat16)
               for kk in ks)
    out = flash_attention(q, k, v, causal=False, bq=bq, bk=bk)
    assert out.dtype == jnp.bfloat16
    ref = attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=4e-2, rtol=4e-2)


def test_flash_attention_casts_operands_after_scaling():
    """``dtype`` sets the dots' operand type: float32 q/k/v in, bfloat16
    operands and output, within bfloat16 rounding of the f32 oracle."""
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q, k, v = (jax.random.normal(kk, (2, 256, 2, 72)) for kk in ks)
    out = flash_attention(q, k, v, causal=False, dtype=jnp.bfloat16)
    assert out.dtype == jnp.bfloat16
    ref = attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref),
                               atol=4e-2, rtol=4e-2)


@pytest.mark.slow
@pytest.mark.parametrize("B,T,H,hd,chunk", [
    (2, 64, 3, 16, 16),
    (1, 128, 2, 32, 32),
    (3, 32, 1, 8, 16),
])
def test_rwkv6_kernel_sweep(B, T, H, hd, chunk):
    ks = jax.random.split(jax.random.PRNGKey(3), 6)
    r = jax.random.normal(ks[0], (B, T, H, hd))
    k = jax.random.normal(ks[1], (B, T, H, hd))
    v = jax.random.normal(ks[2], (B, T, H, hd))
    logw = jnp.clip(-jnp.exp(jax.random.normal(ks[3], (B, T, H, hd))),
                    -8.0, -1e-5)
    u = jax.random.normal(ks[4], (H, hd))
    S0 = jax.random.normal(ks[5], (B, H, hd, hd))
    y, S = rwkv6_wkv(r, k, v, logw, u, S0, chunk=chunk)
    y_ref, S_ref = wkv_ref(r, k, v, logw, u, S0)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(S), np.asarray(S_ref),
                               atol=1e-4, rtol=1e-4)


@pytest.mark.slow
def test_rwkv6_kernel_bf16_inputs():
    ks = jax.random.split(jax.random.PRNGKey(4), 6)
    B, T, H, hd = 1, 32, 2, 16
    r = jax.random.normal(ks[0], (B, T, H, hd), jnp.bfloat16)
    k = jax.random.normal(ks[1], (B, T, H, hd), jnp.bfloat16)
    v = jax.random.normal(ks[2], (B, T, H, hd), jnp.bfloat16)
    logw = jnp.clip(-jnp.exp(jax.random.normal(ks[3], (B, T, H, hd))),
                    -8.0, -1e-5)
    u = jax.random.normal(ks[4], (H, hd))
    S0 = jnp.zeros((B, H, hd, hd))
    y, S = rwkv6_wkv(r, k, v, logw, u, S0, chunk=16)
    y_ref, _ = wkv_ref(r, k, v, logw, u, S0)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               atol=5e-2, rtol=5e-2)


def test_ops_dispatch_cpu_uses_jnp():
    """On CPU 'auto' must route to the jnp oracle (interpret mode is a
    Python emulator — correct but slow for production paths)."""
    from repro.kernels import ops
    assert not ops.on_tpu()
    x = jnp.ones((8,))
    buf = jnp.ones((2, 8))
    xi = jnp.zeros((8,))
    coeffs = jnp.asarray([1.0, 0.0, 0.5, 0.5])
    out = ops.sa_update(x, buf, xi, coeffs)
    np.testing.assert_allclose(np.asarray(out), 2.0)
