"""Shared model machinery: parameter schemas, sharding rules, layers.

Parameters are declared once as ``ParamDef(shape, init, axes)`` where ``axes``
are *logical* axis names ("vocab", "embed", "heads", "mlp", "experts", ...).
``init_params`` materializes the tree; ``specs_for`` maps logical axes to
mesh axes through a strategy rule table, resolving collisions (a mesh axis is
used at most once per param). This keeps init shapes and partition specs in
one place so they cannot drift.

All matmuls run in the config compute dtype (bf16 default) with f32 norms,
softmax and losses.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

Pytree = Any

# ---------------------------------------------------------------------------
# Parameter schema
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ParamDef:
    shape: tuple[int, ...]
    axes: tuple[str | None, ...]  # logical axis per dim (None = replicated)
    init: str = "normal"  # "normal" | "zeros" | "ones" | "scaled"
    scale: float = 1.0

    def materialize(self, key: jax.Array, dtype) -> jnp.ndarray:
        if self.init == "zeros":
            return jnp.zeros(self.shape, dtype)
        if self.init == "ones":
            return jnp.ones(self.shape, dtype)
        if self.init == "normal":
            return (self.scale * jax.random.normal(key, self.shape)).astype(dtype)
        if self.init == "scaled":  # fan-in scaled
            fan_in = self.shape[-2] if len(self.shape) >= 2 else self.shape[-1]
            s = self.scale / math.sqrt(fan_in)
            return (s * jax.random.normal(key, self.shape)).astype(dtype)
        raise ValueError(self.init)


def tree_defs_map(fn: Callable[[ParamDef], Any], defs: Pytree) -> Pytree:
    return jax.tree.map(fn, defs, is_leaf=lambda x: isinstance(x, ParamDef))


def init_params(key: jax.Array, defs: Pytree, dtype=jnp.float32) -> Pytree:
    leaves, treedef = jax.tree.flatten(
        defs, is_leaf=lambda x: isinstance(x, ParamDef)
    )
    keys = jax.random.split(key, len(leaves))
    vals = [d.materialize(k, dtype) for d, k in zip(leaves, keys)]
    return jax.tree.unflatten(treedef, vals)


def abstract_params(defs: Pytree, dtype=jnp.float32) -> Pytree:
    """ShapeDtypeStructs — used by the dry-run; never allocates."""
    return tree_defs_map(lambda d: jax.ShapeDtypeStruct(d.shape, dtype), defs)


# ---------------------------------------------------------------------------
# Sharding strategies
# ---------------------------------------------------------------------------

# logical axis -> mesh axis, tried in order; a mesh axis is consumed at most
# once per param (first match wins).
STRATEGIES: dict[str, dict[str, str]] = {
    # pure tensor parallel (weights replicated across data)
    "tp": {
        "vocab": "model", "heads": "model", "kv_heads": "model",
        "mlp": "model", "experts": "model", "heads_flat": "model",
        "ssm_heads": "model", "moe_ff": None,
    },
    # tensor parallel + fully-sharded remaining dim over EVERY data-parallel
    # rank — ("pod","data") in the multi-pod mesh — (ZeRO-3-ish storage).
    # NOTE (EXPERIMENTS.md §Perf, deepseek D2 — refuted): full EP with
    # experts over ("data","model") makes the gather-based dispatch
    # all-gather the TOKENS across data (2.4 TB/layer) — 1.8x WORSE than
    # the per-layer weight gathers it removes; a ragged all-to-all
    # primitive would be required to express true EP dispatch. Kept at
    # experts -> 'model' (EP=16) with f FSDP-stored over ("pod","data").
    "fsdp_tp": {
        "vocab": "model", "heads": "model", "kv_heads": "model",
        "mlp": "model", "experts": "model", "heads_flat": "model",
        "ssm_heads": "model", "embed": ("pod", "data"),
        "moe_ff": ("pod", "data"),
    },
    # data parallel only (small models / tests)
    "dp": {},
    # serving: weights fully resident (no per-step FSDP gathers), 2D TP —
    # attention/experts over 'model', the MLP hidden dim over 'data'
    # (h @ wo partial-sums all-reduce over data; no weight gathers at all)
    "serve_2d": {
        "vocab": "model", "heads": "model", "kv_heads": "model",
        "experts": "model", "mlp": "data", "heads_flat": "model",
        "ssm_heads": "model", "moe_ff": "data",
    },
}


def resolve_spec(axes: tuple[str | None, ...], rules: dict[str, str],
                 mesh_shape: dict[str, int],
                 shape: tuple[int, ...] | None = None) -> P:
    """Map logical axes -> mesh axes; a mesh axis is consumed once per param
    and a mapping is dropped unless the dim divides the mesh-axis size.
    A rule value may be a TUPLE of mesh axes (e.g. ("pod", "data") for
    FSDP storage over every data-parallel rank in the multi-pod mesh);
    absent axes are filtered and the dim must divide the product."""
    used: set[str] = set()
    out = []
    for i, a in enumerate(axes):
        m = rules.get(a) if a else None
        if isinstance(m, tuple):
            cand = tuple(x for x in m if x in mesh_shape and x not in used)
            placed = False
            # try the full combination, then progressively drop trailing
            # axes, then each single axis (e.g. experts=("data","model"):
            # deepseek's 256 experts take both axes, dbrx's 16 fall back
            # to one)
            options = [cand[:k] for k in range(len(cand), 1, -1)] + \
                      [(x,) for x in cand]
            for opt in options:
                size = math.prod(mesh_shape[x] for x in opt)
                if shape is None or (size > 0 and shape[i] % size == 0):
                    used.update(opt)
                    out.append(opt if len(opt) > 1 else opt[0])
                    placed = True
                    break
            if not placed:
                out.append(None)
            continue
        ok = m is not None and m in mesh_shape and m not in used
        if ok and shape is not None and shape[i] % mesh_shape[m] != 0:
            ok = False
        if ok:
            used.add(m)
            out.append(m)
        else:
            out.append(None)
    return P(*out)


def mesh_shape_dict(mesh) -> dict[str, int]:
    return dict(mesh.shape)


def specs_for(defs: Pytree, strategy: str, mesh) -> Pytree:
    rules = STRATEGIES[strategy]
    ms = mesh_shape_dict(mesh)
    return tree_defs_map(lambda d: resolve_spec(d.axes, rules, ms, d.shape), defs)


def batch_spec(mesh_axes: tuple[str, ...], *trailing) -> P:
    """Batch dim over ('pod','data') when present, else ('data',)."""
    b = tuple(a for a in ("pod", "data") if a in mesh_axes)
    return P(b if b else None, *trailing)


def constrain(x, spec: P):
    """Sharding constraint that is a no-op outside a mesh context."""
    try:
        return jax.lax.with_sharding_constraint(x, spec)
    except (ValueError, RuntimeError):
        return x


# --- activation sharding ----------------------------------------------------
# With FSDP-style weights (embed -> 'data') AND batch -> 'data', GSPMD's
# solver can resolve the axis conflict by replicating the batch (all-gather
# activations, weight-stationary) instead of re-gathering one layer's
# weights at a time. That turns 400 MB/device of activations into the full
# global batch (measured: 1 TB/device for starcoder2-3b train_4k). The fix
# is the standard one (MaxText does the same): explicit constraints pinning
# the residual-stream batch dim at every layer boundary. Models call
# ``shard_batch_dim`` on [B, ...] activations; launch/cells.py installs the
# mesh batch axes for the duration of the lowering.

_BATCH_AXES: tuple[str, ...] | None = None
_SEQ_AXES: tuple[str, ...] | None = None
_SEQ_DIVISOR: int = 1
_MESH_SIZES: dict[str, int] | None = None


class activation_sharding:
    """Context manager: pin [B, S, ...] activations to these mesh axes.

    ``seq_axes`` adds Megatron-style sequence parallelism: the residual
    stream between blocks is sharded over the model axis on its seq dim
    (the per-step layer-input checkpoints of an 88-layer remat'd scan are
    [L, B, S, d] — 66 GB/device for granite train_4k without SP, /16 with).
    GSPMD inserts the SP all-gather before attention/MLP and the
    reduce-scatter after, exactly the Megatron-SP schedule. Applied only
    when S is divisible (decode S=1 opts out automatically).
    """

    def __init__(self, axes, seq_axes=None, seq_divisor: int = 1,
                 mesh_sizes: dict | None = None):
        self.axes = tuple(axes) if axes else None
        self.seq_axes = tuple(seq_axes) if seq_axes else None
        self.seq_divisor = seq_divisor
        self.mesh_sizes = mesh_sizes

    def __enter__(self):
        global _BATCH_AXES, _SEQ_AXES, _SEQ_DIVISOR, _MESH_SIZES
        self._old = (_BATCH_AXES, _SEQ_AXES, _SEQ_DIVISOR, _MESH_SIZES)
        _BATCH_AXES = self.axes
        _SEQ_AXES = self.seq_axes
        _SEQ_DIVISOR = self.seq_divisor
        _MESH_SIZES = self.mesh_sizes
        return self

    def __exit__(self, *exc):
        global _BATCH_AXES, _SEQ_AXES, _SEQ_DIVISOR, _MESH_SIZES
        _BATCH_AXES, _SEQ_AXES, _SEQ_DIVISOR, _MESH_SIZES = self._old
        return False


def shard_batch_dim(x):
    """Constrain dim 0 (batch) — and dim 1 (sequence, when SP is on and
    divisible) — of an activation to the installed mesh axes."""
    if _BATCH_AXES is None or x.ndim < 2:
        return x
    dims: list = [_BATCH_AXES] + [None] * (x.ndim - 1)
    if (_SEQ_AXES is not None and x.ndim >= 3
            and x.shape[1] % max(_SEQ_DIVISOR, 1) == 0 and x.shape[1] > 1):
        dims[1] = _SEQ_AXES
    return jax.lax.with_sharding_constraint(x, P(*dims))


def shard_logits_path(h, logits):
    """At the LM head the S-sharded residual stream meets the V-sharded
    head matrix — both on 'model'. Unconstrained, GSPMD gathers the WHOLE
    head (3.4 GiB f32 for deepseek's 129k vocab, hoisted out of the
    microbatch scan). Pin: gather h's sequence (59 MB), keep V sharded."""
    if _BATCH_AXES is None:
        return h, logits
    if h is not None and h.ndim >= 3:
        h = jax.lax.with_sharding_constraint(
            h, P(_BATCH_AXES, *([None] * (h.ndim - 1))))
    if logits is not None and _SEQ_AXES is not None \
            and logits.shape[-1] % max(_SEQ_DIVISOR, 1) == 0:
        dims = [_BATCH_AXES] + [None] * (logits.ndim - 2) + [_SEQ_AXES]
        logits = jax.lax.with_sharding_constraint(logits, P(*dims))
    return h, logits


def shard_moe_dispatch(x):
    """Constrain MoE dispatch tensors [B(groups), E, C, d] to the EP
    layout: experts over ("data","model") when E divides (full EP — each
    device owns its experts, tokens all-to-all to them), else E over
    "model" with groups batch-sharded. Without an explicit constraint
    GSPMD resolves the B-vs-E conflict by gathering the group dim
    (measured: 13 GiB f32 [B_global, E_local, C, f] for dbrx)."""
    if _BATCH_AXES is None or x.ndim < 3:
        return x
    dims: list = [None] * x.ndim
    E = x.shape[1]
    dims[0] = _BATCH_AXES
    if _SEQ_AXES is not None and E % max(_SEQ_DIVISOR, 1) == 0:
        dims[1] = _SEQ_AXES  # the model axis (EP)
    return jax.lax.with_sharding_constraint(x, P(*dims))


def shard_heads_dim(x, dim: int = 2):
    """Constrain the heads dim of [B, S, H, hd] attention internals to the
    model axis (Megatron head-parallel attention). Needed because with SP
    the residual stream is S-sharded over 'model'; without an explicit
    constraint GSPMD may resolve the S-vs-heads conflict by replicating
    the heads (measured: q/k/v and scores fully replicated for zamba2's
    shared block). No-op when heads don't divide or outside a mesh."""
    if _SEQ_AXES is None or x.ndim <= dim:
        return x
    if x.shape[dim] % max(_SEQ_DIVISOR, 1) != 0:
        return x
    dims: list = [_BATCH_AXES] + [None] * (x.ndim - 1)
    dims[dim] = _SEQ_AXES
    return jax.lax.with_sharding_constraint(x, P(*dims))


# ---------------------------------------------------------------------------
# Layers (functional)
# ---------------------------------------------------------------------------


def rms_norm(x, weight, eps: float = 1e-6):
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * (1.0 + weight.astype(jnp.float32))).astype(dt)


def layer_norm(x, weight, bias, eps: float = 1e-5):
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    x = (x - mu) * jax.lax.rsqrt(var + eps)
    return (x * weight.astype(jnp.float32) + bias.astype(jnp.float32)).astype(dt)


def rope_frequencies(head_dim: int, theta: float = 10000.0) -> np.ndarray:
    return 1.0 / (theta ** (np.arange(0, head_dim, 2, dtype=np.float64) / head_dim))


def apply_rope(x, positions, theta: float = 10000.0):
    """x: [..., S, H, hd]; positions: broadcastable to [..., S]."""
    hd = x.shape[-1]
    freqs = jnp.asarray(rope_frequencies(hd, theta), jnp.float32)  # [hd/2]
    ang = positions[..., :, None].astype(jnp.float32) * freqs  # [..., S, hd/2]
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def apply_mrope(x, positions3, sections: tuple[int, int, int], theta: float = 10000.0):
    """Multimodal RoPE (Qwen2-VL): 3 position streams (t, h, w) rotate
    disjoint frequency sections. positions3: [3, ..., S]."""
    hd = x.shape[-1]
    freqs = jnp.asarray(rope_frequencies(hd, theta), jnp.float32)  # [hd/2]
    n = hd // 2
    assert sum(sections) == n, (sections, n)
    sec_id = jnp.asarray(
        np.repeat(np.arange(3), np.asarray(sections)), jnp.int32
    )  # [hd/2]
    # pick the right position stream per frequency
    pos = jnp.take(positions3, sec_id, axis=0)  # [hd/2, ..., S] -> move axis
    pos = jnp.moveaxis(pos, 0, -1)  # [..., S, hd/2]
    ang = pos.astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


ACTIVATIONS = {
    "gelu": jax.nn.gelu,
    "silu": jax.nn.silu,
    "relu": jax.nn.relu,
    "relu2": lambda x: jnp.square(jax.nn.relu(x)),
}


def mlp_defs(d_model: int, d_ff: int, gated: bool) -> dict:
    if gated:
        return {
            "wi": ParamDef((d_model, d_ff), ("embed", "mlp"), "scaled"),
            "wg": ParamDef((d_model, d_ff), ("embed", "mlp"), "scaled"),
            "wo": ParamDef((d_ff, d_model), ("mlp", "embed"), "scaled"),
        }
    return {
        "wi": ParamDef((d_model, d_ff), ("embed", "mlp"), "scaled"),
        "wo": ParamDef((d_ff, d_model), ("mlp", "embed"), "scaled"),
    }


def mlp_apply(p: dict, x, act: str, gated: bool):
    """The MLP; biases ``bi`` and ``bo``, where ``p`` holds them, follow
    the first and the last projection."""
    f = ACTIVATIONS[act]
    h = x @ p["wi"]
    if "bi" in p:
        h = h + p["bi"]
    if gated:
        h = f(x @ p["wg"]) * h
    else:
        h = f(h)
    out = h @ p["wo"]
    return out + p["bo"] if "bo" in p else out


def softmax_cross_entropy(logits, labels, mask=None):
    """logits [..., V] (any dtype; upcast), labels int [...]. Mean over mask.

    Sharding-friendly: the gold logit is extracted with an iota==label mask
    (per-vocab-shard partial sums + all-reduce under GSPMD) instead of
    ``take_along_axis``, which would all-gather a vocab-sharded logits
    tensor (12.9 GB for granite train_4k).
    """
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    V = logits.shape[-1]
    hit = jax.lax.broadcasted_iota(jnp.int32, logits.shape, logits.ndim - 1) \
        == labels[..., None]
    gold = jnp.sum(jnp.where(hit, logits, 0.0), axis=-1)
    nll = logz - gold
    if mask is None:
        return jnp.mean(nll)
    mask = mask.astype(jnp.float32)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def chunked_lm_loss(hidden, head_w, labels, mask=None, *, chunk: int = 512):
    """Sequence-chunked LM loss: logits for one S-chunk at a time.

    For V = 256k (gemma) the full [B, S, V] f32 logits are 4.2 GB/device
    even vocab-sharded; chunking S bounds the live logits to
    [B, chunk, V/shards] and XLA frees each chunk before the next
    (lax.map is sequential). hidden [B, S, d] (pre-head, post-norm),
    head_w [d, V]. Returns mean nll over mask.
    """
    B, S, d = hidden.shape
    if S % chunk or S <= chunk:
        logits = (hidden @ head_w).astype(jnp.float32)
        return softmax_cross_entropy(logits, labels, mask)
    n = S // chunk
    h = hidden.reshape(B, n, chunk, d).swapaxes(0, 1)        # [n,B,c,d]
    y = labels.reshape(B, n, chunk).swapaxes(0, 1)
    m = None if mask is None else mask.reshape(B, n, chunk).swapaxes(0, 1)

    @jax.checkpoint
    def one(args):
        hc, yc, mc = args
        logits = (hc @ head_w).astype(jnp.float32)
        logz = jax.nn.logsumexp(logits, axis=-1)
        hit = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 2) \
            == yc[..., None]
        gold = jnp.sum(jnp.where(hit, logits, 0.0), axis=-1)
        nll = logz - gold
        mc = jnp.ones_like(nll) if mc is None else mc.astype(jnp.float32)
        return jnp.sum(nll * mc), jnp.sum(mc)

    if m is None:
        m = jnp.ones((n, B, chunk), jnp.float32)
    sums, cnts = jax.lax.map(one, (h, y, m))
    return jnp.sum(sums) / jnp.maximum(jnp.sum(cnts), 1.0)
