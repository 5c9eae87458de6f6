"""The main path's Pallas kernels compile for a TPU v5e chip.

Interpret mode (every other kernel test) cannot see what Mosaic refuses:
block shapes that are not tile-aligned, or sublane offsets. These tests
hand the kernels to the TPU compiler for one described, unattached v5e
chip and check that the compiled program holds the kernel
(``tpu_custom_call``): the combine kernels at the DiT-XL/2 latent
(256 x 16) alone and under the serving lanes' ``vmap`` (8 lanes,
per-lane coefficients as in the step function), a latent whose size is
not a multiple of 128, and non-causal flash attention at DiT-XL/2's
head_dim 72.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention import flash_attention
from repro.kernels.sa_fused import sa_fused_update
from repro.kernels.sa_update import sa_update

P = 3  # history rows: SA-Solver's predictor order 3


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def compiled_text(fn, *avals) -> str:
    return jax.jit(fn).lower(*avals).compile().as_text()


def combine_avals(one_chip, shape, dtype, lanes, rows):
    lead = () if lanes is None else (lanes,)
    s = lambda sh, dt: jax.ShapeDtypeStruct(sh, dt, sharding=one_chip)
    return (s(lead + shape, dtype), s(lead + (P,) + shape, dtype),
            s(lead + shape, dtype),
            s(lead + ((rows,) if rows else ()) + (P + 2,), jnp.float32))


@pytest.mark.parametrize("shape", [(256, 16), (250, 16)],
                         ids=["dit-latent", "ragged"])
@pytest.mark.parametrize("lanes", [None, 8], ids=["single", "8-lanes"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("kernel", ["fused", "update"])
def test_combine_kernel_compiles(one_chip, kernel, dtype, lanes, shape):
    if kernel == "fused":
        fn = lambda x, b, xi, c: sa_fused_update(x, b, xi, c,
                                                 interpret=False)
        rows = 2
    else:
        fn = lambda x, b, xi, c: sa_update(x, b, xi, c, interpret=False)
        rows = None
    if lanes is not None:
        fn = jax.vmap(fn)  # per-lane coefficients, as in a step
    avals = combine_avals(one_chip, shape, dtype, lanes, rows)
    assert "tpu_custom_call" in compiled_text(fn, *avals)


def test_combine_kernel_compiles_with_shared_coefficients(one_chip):
    """The whole-solve executor's case: lanes batched, the step's
    coefficients shared by every lane."""
    fn = jax.vmap(lambda x, b, xi, c: sa_fused_update(x, b, xi, c,
                                                      interpret=False),
                  in_axes=(0, 0, 0, None))
    x, b, xi, _ = combine_avals(one_chip, (256, 16), jnp.bfloat16, 8, 2)
    c = jax.ShapeDtypeStruct((2, P + 2), jnp.float32, sharding=one_chip)
    assert "tpu_custom_call" in compiled_text(fn, x, b, xi, c)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_flash_attention_compiles_at_dit_head_dim(one_chip, dtype):
    q = jax.ShapeDtypeStruct((2, 16, 256, 72), dtype, sharding=one_chip)
    fn = lambda q, k, v: flash_attention(q, k, v, causal=False,
                                         interpret=False)
    assert "tpu_custom_call" in compiled_text(fn, q, q, q)
