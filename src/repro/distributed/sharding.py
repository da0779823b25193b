"""Logical-axis sharding (MaxText-style), divisibility-aware.

Every parameter and activation is annotated with *logical* axis names
("batch", "heads", "ffn", ...).  A rule table maps each logical name to
an ordered tuple of mesh axes to try; the resolver takes the maximal
prefix of candidates whose cumulative product divides the dimension and
whose mesh axes are not already used in the same spec.  A mesh axis is
*skipped, never force-fit*: a 40-head dim on a 16-way "model" axis
resolves to unsharded rather than erroring, and the roofline table shows
the cost (that is a feature: baselines stay honest, hillclimbs fix them).

``use_sharding(mesh, rules)`` installs a context; ``constrain(x, *axes)``
is a no-op outside it, so model code is runnable un-meshed (CPU smoke
tests) and sharded (dry-run / production) without change.
"""

from __future__ import annotations

import contextlib
import dataclasses
import threading
from typing import Mapping, Sequence

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec


# ---------------------------------------------------------------------------
# Rule tables


def _merge(*dicts) -> dict:
    out: dict = {}
    for d in dicts:
        out.update(d)
    return out


# Parameters.  "embed" marks the d_model-ish dim of weight matrices; in
# fsdp_tp mode it shards over "data" (ZeRO-3: XLA all-gathers per layer).
PARAM_RULES_TP: dict = {
    "layers": (),            # scan-stacked leading axis
    "embed": (),
    "heads": ("model",),
    "kv_heads": ("model",),
    "head_dim": (),
    "qkv": (),
    "ffn": ("model",),
    "experts": ("model",),
    "vocab": ("model",),
    "conv_dim": ("model",),
    "ssm_heads": ("model",),
    "ssm_state": (),
    None: (),
}

PARAM_RULES_FSDP_TP = _merge(PARAM_RULES_TP, {"embed": ("data",)})

# Activations.
ACT_RULES_BASE: dict = {
    "batch": ("pod", "data"),
    # Fleet batching (core.pipeline.VisualSystem.process_fleet): the
    # leading rig axis of a multi-rig frame batch is data-parallel.
    "rig": ("pod", "data"),
    "seq": (),               # context-parallel knob rewires to ("model",)
    # Megatron-style sequence parallelism: the RESIDUAL STREAM (and the
    # saved per-layer activations) shard their seq dim over "model";
    # XLA turns each block's TP all-reduce into all-gather + reduce-
    # scatter (same wire volume, 16x less activation memory).
    "res_seq": ("model",),
    # logits ALWAYS prefer vocab-sharding over seq-sharding: the loss
    # reduces over vocab, and full-vocab gather/one-hot buffers at 256k
    # vocab would dominate memory if seq grabbed the model axis first
    "logits_seq": (),
    "act_embed": (),
    "heads": ("model",),
    "kv_heads": ("model",),
    "head_dim": (),
    "ffn": ("model",),
    "experts": ("model",),
    "capacity": (),
    "vocab": ("model",),
    "cache_seq": (),         # decode policy rewires to ("model",) etc.
    "ssm_heads": ("model",),
    "ssm_state": (),
    "conv_dim": ("model",),
    "layers": (),
    None: (),
}


@dataclasses.dataclass(frozen=True)
class Rules:
    """A resolved pair of rule tables for one (config x shape) cell."""

    params: Mapping[str, tuple]
    acts: Mapping[str, tuple]

    @staticmethod
    def make(sharding_mode: str = "fsdp_tp",
             seq_axes: tuple = (),
             cache_seq_axes: tuple = (),
             extra_acts: Mapping[str, tuple] | None = None,
             extra_params: Mapping[str, tuple] | None = None) -> "Rules":
        params = (PARAM_RULES_FSDP_TP if sharding_mode == "fsdp_tp"
                  else PARAM_RULES_TP)
        acts = _merge(ACT_RULES_BASE,
                      {"seq": tuple(seq_axes),
                       "cache_seq": tuple(cache_seq_axes)},
                      dict(extra_acts or {}))
        return Rules(params=_merge(params, dict(extra_params or {})),
                     acts=dict(acts))


# ---------------------------------------------------------------------------
# Resolver


def resolve(rules: Mapping[str, tuple], axes: Sequence[str | None],
            shape: Sequence[int], mesh: Mesh) -> PartitionSpec:
    """Logical axes -> PartitionSpec under divisibility + no-reuse."""
    assert len(axes) == len(shape), (axes, shape)
    sizes = dict(mesh.shape)        # works for Mesh and AbstractMesh
    used: set = set()
    out = []
    for name, dim in zip(axes, shape):
        cand = rules.get(name, ())
        picked: list = []
        prod = 1
        for ax in cand:
            if ax in used or ax not in sizes:
                continue
            if dim % (prod * sizes[ax]) != 0:
                break                      # maximal divisible prefix
            picked.append(ax)
            prod *= sizes[ax]
        used.update(picked)
        if not picked:
            out.append(None)
        elif len(picked) == 1:
            out.append(picked[0])
        else:
            out.append(tuple(picked))
    # strip trailing Nones for a tidy spec
    while out and out[-1] is None:
        out.pop()
    return PartitionSpec(*out)


# ---------------------------------------------------------------------------
# Context


class _Ctx(threading.local):
    def __init__(self):
        self.stack: list = []


_CTX = _Ctx()


@dataclasses.dataclass(frozen=True)
class ShardingCtx:
    mesh: Mesh
    rules: Rules


def current_ctx() -> ShardingCtx | None:
    return _CTX.stack[-1] if _CTX.stack else None


@contextlib.contextmanager
def use_sharding(mesh: Mesh, rules: Rules):
    _CTX.stack.append(ShardingCtx(mesh=mesh, rules=rules))
    try:
        yield
    finally:
        _CTX.stack.pop()


def constrain(x: jax.Array, *axes: str | None) -> jax.Array:
    """Annotate an activation with logical axes (no-op without a mesh)."""
    ctx = current_ctx()
    if ctx is None:
        return x
    spec = resolve(ctx.rules.acts, axes, x.shape, ctx.mesh)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(ctx.mesh, spec))


def shard_over(fn, mesh: Mesh, axis: str, arg_axis: int = 0):
    """``shard_map`` a single-argument function over ONE named mesh
    axis: dimension ``arg_axis`` of the argument is split across
    ``axis`` and every output leaf keeps that axis as its leading
    dimension.  Used by ``core.pipeline.VisualSystem`` to shard the
    fleet rig axis; the per-device program is the unmodified fused
    3-launch datapath.  ``check_vma`` is off because the Pallas calls
    inside carry no varying-manual-axes annotations."""
    in_spec = PartitionSpec(*([None] * arg_axis + [axis]))
    return jax.shard_map(fn, mesh=mesh, in_specs=(in_spec,),
                         out_specs=in_spec, check_vma=False)


def spec_for(axes: Sequence[str | None], shape: Sequence[int],
             kind: str = "param") -> PartitionSpec:
    """Resolve a spec with the installed context (for in/out_shardings)."""
    ctx = current_ctx()
    assert ctx is not None, "spec_for needs use_sharding()"
    rules = ctx.rules.params if kind == "param" else ctx.rules.acts
    return resolve(rules, axes, shape, ctx.mesh)
