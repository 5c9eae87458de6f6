"""The main path's Pallas kernels compile for a TPU v5e chip.

Interpret mode (every other kernel test) cannot see what Mosaic refuses:
block shapes that are not tile-aligned, or sublane offsets. These tests
hand the kernels to the TPU compiler for one described, unattached v5e
chip and check that the compiled program holds the kernel
(``tpu_custom_call``): the combine kernels at the DiT-XL/2 latent
(256 x 16) alone and under the serving lanes' ``vmap`` (8 lanes,
per-lane coefficients as in the step function), a latent whose size is
not a multiple of 128, non-causal flash attention at DiT-XL/2's
head_dim 72 at 256 and 1024 tokens and at SD3 Medium's joint layout
(4250 tokens, 24 heads of 64), a DiT-XL/2-width denoiser layer whose
attention the program dispatches to that kernel on one chip, and keeps
on the jnp path with its lanes split over the four chips, and two SD3
Medium-width MMDiT blocks whose joint attentions are the kernel.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library.
"""

import dataclasses
import re

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
    fn = lambda q, k, v: flash_attention(q, k, v, causal=False,
                                         interpret=False)
    for tokens in (256, 1024):  # DiT-XL/2 at 256 and 512 px
        q = jax.ShapeDtypeStruct((2, tokens, 16, 72), dtype,
                                 sharding=one_chip)
        assert "tpu_custom_call" in compiled_text(fn, q, q, q)


_OP_NAME = re.compile(r'op_name="([^"]*)"')


def _in_attention(op_name):
    return "attention" in re.split(r"[/();]", op_name)


@pytest.mark.parametrize("tokens", [256, 1024])
def test_dit_denoise_runs_attention_as_the_kernel(one_chip, monkeypatch,
                                                  tokens):
    """A 1-layer DiT-XL/2-width ``denoise`` (d 1152, 16 heads, head_dim
    72), as the serving lanes call it (lanes x the CFG pair, vmapped),
    compiled for the chip: where ``use_kernel`` picks the kernel, its
    attention is the Pallas kernel under the ``attention`` name scope and
    no jnp score product is left there; elsewhere it is the jnp path."""
    from repro.models import attention
    kernel = attention.use_kernel("tpu", tokens, tokens, False, None, False)
    # the program asks the default backend, which is the CPU here
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    calls, scores = _dit_layer_attention(one_chip, one_chip, tokens)
    if kernel:
        assert calls and all(_in_attention(op) for op in calls)
        assert not scores
    else:
        assert not calls and scores


def test_dit_denoise_on_a_mesh_keeps_jnp_attention(topo, monkeypatch):
    """The same layer with the lanes split over the four chips of the
    described v5e:2x2 (``--sharded`` serving, the request axis over
    ``data``): XLA cannot partition a Pallas call, so the lowering keeps
    the jnp path there, and the program compiles."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mesh = Mesh(topo.devices, ("data",))
    calls, scores = _dit_layer_attention(
        NamedSharding(mesh, PartitionSpec()),
        NamedSharding(mesh, PartitionSpec("data")), 1024)
    assert not calls and scores


def _dit_layer_attention(replicated, lanes_sharding, tokens):
    """Compile a 1-layer DiT-XL/2-width ``denoise`` for 8 lanes x the CFG
    pair (vmapped, as the serving lanes call it); return the op names of
    its Pallas calls and whether a jnp score product sits under the
    ``attention`` scope."""
    from repro.configs import dit_xl_2
    from repro.models import build_model, init_params
    model = build_model(dataclasses.replace(dit_xl_2.full(), n_layers=1))
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=replicated),
        jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0),
                                           model.param_defs())))

    def lanes(p, z, t):
        one = lambda zz: model.denoise(p, zz[None], t)[0]
        return jax.vmap(jax.vmap(one))(z)

    z = jax.ShapeDtypeStruct((8, 2, tokens, dit_xl_2.LATENT_DIM),
                             jnp.float32, sharding=lanes_sharding)
    t = jax.ShapeDtypeStruct((), jnp.float32, sharding=replicated)
    text = compiled_text(lanes, params, z, t)
    calls = [_OP_NAME.search(ln).group(1) for ln in text.splitlines()
             if "tpu_custom_call" in ln and " custom-call(" in ln]
    att = [op for op in _OP_NAME.findall(text) if _in_attention(op)]
    return calls, any("bskgd,btkd->bkgst" in op for op in att)


def test_flash_attention_compiles_at_the_joint_layout(one_chip):
    """SD3 Medium's joint attention: 4096 image and 154 text tokens, a
    ragged 4250, 24 heads of 64 merged into 1536 columns, at the blocks
    ``_block_sizes`` picks for it."""
    fn = lambda q, k, v: flash_attention(q, k, v, causal=False,
                                         dtype=jnp.bfloat16, interpret=False)
    q = jax.ShapeDtypeStruct((2, 4250, 24, 64), jnp.bfloat16,
                             sharding=one_chip)
    assert "tpu_custom_call" in compiled_text(fn, q, q, q)


def test_mmdit_denoise_runs_joint_attention_as_the_kernel(one_chip,
                                                          monkeypatch):
    """A 2-block SD3 Medium-width MMDiT (a full joint block and the
    pre-only last one) over 4096 image and 154 text tokens, for 4 lanes
    x the CFG pair (vmapped, as the serving lanes call it), compiled for
    the chip: both joint attentions are the Pallas kernel under the
    ``attention`` scope, and the text stream's ops carry the
    ``context_stream`` scope."""
    from repro.configs import get_config
    from repro.models import abstract_params, build_model
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    model = build_model(dataclasses.replace(get_config("sd3-medium"),
                                            n_layers=2))
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        abstract_params(model.param_defs()))
    s = lambda *sh: jax.ShapeDtypeStruct(sh, jnp.float32, sharding=one_chip)

    def lanes(p, z, t, c):
        one = lambda zz, cc: model.denoise(
            p, zz[None], t, jax.tree.map(lambda a: a[None], cc))[0]
        return jax.vmap(jax.vmap(one))(z, c)

    text = compiled_text(lanes, params, s(4, 2, 4096, 64), s(),
                         {"seq": s(4, 2, 154, 4096), "pooled": s(4, 2, 2048)})
    calls = [_OP_NAME.search(ln).group(1) for ln in text.splitlines()
             if "tpu_custom_call" in ln and " custom-call(" in ln]
    assert len(calls) == 2 and all(_in_attention(op) for op in calls)
    assert any("context_stream" in re.split(r"[/();]", op)
               for op in _OP_NAME.findall(text))


def _weight_converts(text, shapes):
    """The converts in ``text`` whose result has the shape of a weight
    stack in ``shapes`` (the [L, ...] leaves)."""
    dims = {",".join(map(str, s)) for s in shapes}
    return [ln for ln in text.splitlines() if " convert(" in ln
            and re.search(r"= (?:bf16|f32)\[([\d,]+)\]", ln)
            and re.search(r"= (?:bf16|f32)\[([\d,]+)\]", ln).group(1) in dims]


def test_step_program_takes_weight_copies_without_converts(one_chip):
    """The step scheduler's program for a 2-block DiT-XL/2-width
    denoiser (8 lanes, CFG, the step cell's SA-Solver), compiled for the
    chip: fed the f32 weights it rounds each weight stack to bf16 inside
    (a convert per stack on every tick); fed the step path's copies,
    upcast to f32 at its top, XLA folds the converts away and none of a
    weight stack's shape is left."""
    from repro.configs import dit_xl_2
    from repro.core import Denoiser, get_schedule
    from repro.core.samplers import (SamplerSpec, build_plan, fresh_carry,
                                     make_stepfns)
    from repro.core.samplers.stepwise import _StepWeights
    from repro.launch.sample import as_prediction_network
    from repro.models import abstract_params, build_model
    model = build_model(dataclasses.replace(dit_xl_2.full(), n_layers=2))
    sched = get_schedule("vp_linear")
    on_chip = lambda t: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        t)
    params = on_chip(abstract_params(model.param_defs()))
    den = Denoiser(as_prediction_network(model, sched, "eps"), sched,
                   prediction="eps", guidance=True, params=params)
    spec = SamplerSpec.from_nfe("sa", 20, schedule=sched, predictor_order=3,
                                corrector_order=1, tau=1.0, prediction="eps",
                                guidance=True)
    plan = build_plan(spec)
    shape, cond = (256, dit_xl_2.LATENT_DIM), \
        jnp.zeros((dit_xl_2.LATENT_DIM,), jnp.float32)
    fns = make_stepfns(plan, den, shape, jnp.float32, 8, cond=cond)
    arrays = on_chip(jax.eval_shape(lambda: fns.adapter.arrays(plan)))
    carry = on_chip(jax.eval_shape(lambda: fresh_carry(
        plan, 8, shape, jnp.float32, cond=cond)))
    leaves, treedef = jax.tree.flatten(params)
    rounded = tuple(treedef.flatten_up_to(den.network.dot_weights))
    copies = _StepWeights(
        [jax.ShapeDtypeStruct(a.shape, jnp.bfloat16, sharding=one_chip)
         if r else a for a, r in zip(leaves, rounded)], treedef, rounded)
    stacks = [a.shape for a, r in zip(leaves, rounded) if r]
    assert len(stacks) == 7  # adaLN, q, k, v, o, MLP in and out
    f32 = fns._step.lower(arrays, carry, params).compile().as_text()
    bf16 = fns._step.lower(arrays, carry, copies).compile().as_text()
    assert len(_weight_converts(f32, stacks)) >= len(stacks)
    assert not _weight_converts(bf16, stacks)
