"""Feed-forward blocks: gated MLP (SwiGLU / GeGLU) and token-choice MoE.

MoE is the TPU-native static-shape dispatch: top-k routing -> capacity-
bounded slotting (scatter token indices into an (E, C) slot table) ->
per-expert batched matmuls (E-sharded) -> weighted scatter-add combine.
FLOPs scale with ACTIVE experts (top_k), not total experts, unlike the
dense one-hot dispatch einsum.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.distributed.sharding import constrain
from repro.models import layers
from repro.models.params import P


# ---------------------------------------------------------------------------
# Dense gated MLP

def mlp_schema(d_model: int, d_ff: int) -> dict:
    return {
        "wi_gate": P((d_model, d_ff), ("embed", "ffn")),
        "wi_up": P((d_model, d_ff), ("embed", "ffn")),
        "wo": P((d_ff, d_model), ("ffn", "embed")),
    }


def mlp(params, x: jnp.ndarray, act: str = "silu") -> jnp.ndarray:
    f = layers.act_fn(act)
    g = jnp.einsum("bsd,df->bsf", x, params["wi_gate"].astype(x.dtype))
    u = jnp.einsum("bsd,df->bsf", x, params["wi_up"].astype(x.dtype))
    h = constrain(f(g) * u, "batch", "seq", "ffn")
    y = jnp.einsum("bsf,fd->bsd", h, params["wo"].astype(x.dtype))
    return constrain(y, "batch", "res_seq", "act_embed")


# ---------------------------------------------------------------------------
# Mixture of Experts

@dataclasses.dataclass(frozen=True)
class MoESpec:
    d_model: int
    d_ff: int                 # per-expert intermediate size
    n_experts: int
    top_k: int
    n_shared: int = 0         # always-active shared experts (fused as one)
    capacity_factor: float = 1.25
    act: str = "silu"
    impl: str = "a2a"         # a2a (shard_map EP) | gather (SPMD einsum)

    def capacity(self, tokens: int) -> int:
        c = int(self.capacity_factor * self.top_k * tokens
                / self.n_experts)
        return max(8, ((c + 7) // 8) * 8)    # pad for lane alignment


def moe_schema(s: MoESpec) -> dict:
    e, d, f = s.n_experts, s.d_model, s.d_ff
    out = {
        "router": P((d, e), ("embed", "experts"), scale=d ** -0.5),
        "wi_gate": P((e, d, f), ("experts", "embed", "ffn")),
        "wi_up": P((e, d, f), ("experts", "embed", "ffn")),
        "wo": P((e, f, d), ("experts", "ffn", "embed")),
    }
    if s.n_shared:
        out["shared"] = mlp_schema(d, s.n_shared * f)
    return out


def router_probs(params, x: jnp.ndarray, s: MoESpec):
    """Top-k routing.  Returns (expert_idx (T, k), gates (T, k), aux_loss)
    where T = B * S and gates renormalize over the selected k."""
    logits = jnp.einsum("td,de->te", x.astype(jnp.float32),
                        params["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, s.top_k)
    gates = gates / jnp.maximum(jnp.sum(gates, -1, keepdims=True), 1e-9)
    # Switch-style load-balance loss: E * sum_e f_e * p_e
    me = jnp.mean(probs, axis=0)
    one_hot = jax.nn.one_hot(idx[:, 0], s.n_experts)   # top-1 assignment
    ce = jnp.mean(one_hot, axis=0)
    aux = s.n_experts * jnp.sum(me * ce)
    return idx, gates.astype(x.dtype), aux


def moe_a2a(params, x: jnp.ndarray, s: MoESpec):
    """Expert parallelism via shard_map + all_to_all (the GShard/Switch
    TPU pattern).

    Tokens stay where they live (batch over (pod, data), seq over
    model); each device routes its LOCAL tokens into per-expert slot
    blocks, one all_to_all over the `model` axis moves each block to
    its expert's owner, the expert FFN runs data-parallel, and the
    reverse all_to_all brings outputs home for a local combine.  Wire
    cost per device ~= 2 x (k x T_local x d) instead of the SPMD
    gather's all-gather of the full global slot tensor (~16x less),
    and expert compute is data-parallel instead of replicated.

    Expert weights are FSDP-sharded on their d_model dim; they are
    gathered per layer over `data` in bf16 (half the wire of the f32
    gathers XLA emits for the einsum formulation).
    """
    import functools

    from jax import shard_map
    from jax.sharding import PartitionSpec as Ps

    from repro.distributed.sharding import current_ctx, resolve

    ctx = current_ctx()
    if ctx is None:
        return _moe_gather(params, x, s)   # un-meshed (smoke/CPU)
    mesh = ctx.mesh
    sizes = dict(mesh.shape)
    tp = sizes.get("model", 1)
    if s.n_experts % max(tp, 1) != 0 or tp == 1:
        return _moe_gather(params, x, s)

    x_spec = resolve(ctx.rules.acts, ("batch", "res_seq", "act_embed"),
                     x.shape, mesh)
    r_spec = resolve(ctx.rules.params, ("embed", "experts"),
                     params["router"].shape, mesh)
    w_axes = ("experts", "embed", "ffn")
    wi_spec = resolve(ctx.rules.params, w_axes,
                      params["wi_gate"].shape, mesh)
    wo_spec = resolve(ctx.rules.params, ("experts", "ffn", "embed"),
                      params["wo"].shape, mesh)
    seq_sharded = len(x_spec) > 1 and x_spec[1] is not None
    all_axes = tuple(mesh.axis_names)

    def gather_axes(w, spec, skip_dim=0):
        """all_gather a param over every sharded dim except skip_dim
        (the expert dim stays local), in the compute dtype."""
        w = w.astype(x.dtype)
        for dim, ax in enumerate(spec):
            if ax is None or dim == skip_dim:
                continue
            for a in ((ax,) if isinstance(ax, str) else ax):
                w = jax.lax.all_gather(w, a, axis=dim, tiled=True)
        return w

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(x_spec, r_spec, wi_spec, wi_spec, wo_spec),
        out_specs=(x_spec, Ps()), check_vma=False)
    def run(x_l, router_l, wg_l, wu_l, wo_l):
        b_l, s_l, d = x_l.shape
        t_l = b_l * s_l
        xt = x_l.reshape(t_l, d)
        # gather router fully (tiny), expert weights over FSDP dims
        router = router_l.astype(jnp.float32)
        for dim, ax in enumerate(r_spec):
            if ax is None:
                continue
            for a in ((ax,) if isinstance(ax, str) else ax):
                router = jax.lax.all_gather(router, a, axis=dim,
                                            tiled=True)
        wg = gather_axes(wg_l, wi_spec)
        wu = gather_axes(wu_l, wi_spec)
        wo = gather_axes(wo_l, wo_spec)

        logits = xt.astype(jnp.float32) @ router
        probs = jax.nn.softmax(logits, axis=-1)
        gates, idx = jax.lax.top_k(probs, s.top_k)
        gates = (gates / jnp.maximum(jnp.sum(gates, -1, keepdims=True),
                                     1e-9)).astype(x_l.dtype)
        me = jnp.mean(probs, axis=0)
        ce = jnp.mean(jax.nn.one_hot(idx[:, 0], s.n_experts), axis=0)
        aux = jax.lax.pmean(s.n_experts * jnp.sum(me * ce), all_axes)

        # local slotting (static shapes)
        cap = s.capacity(t_l)
        flat_e = idx.reshape(-1)
        one_hot = jax.nn.one_hot(flat_e, s.n_experts, dtype=jnp.int32)
        pos = jnp.sum(jnp.cumsum(one_hot, axis=0) * one_hot, -1) - 1
        keep = pos < cap
        tok_ids = jnp.repeat(jnp.arange(t_l), s.top_k)
        e_ids = jnp.where(keep, flat_e, s.n_experts)
        c_ids = jnp.where(keep, pos, 0)
        slot_tok = jnp.full((s.n_experts, cap), t_l, jnp.int32)
        slot_gate = jnp.zeros((s.n_experts, cap), x_l.dtype)
        slot_tok = slot_tok.at[(e_ids, c_ids)].set(
            jnp.where(keep, tok_ids, t_l), mode="drop")
        slot_gate = slot_gate.at[(e_ids, c_ids)].set(
            jnp.where(keep, gates.reshape(-1), 0.0), mode="drop")
        xt_pad = jnp.concatenate([xt, jnp.zeros((1, d), xt.dtype)], 0)
        xe = xt_pad[slot_tok]                      # (E, C_l, d) local

        # a2a: expert blocks to their owners (model axis)
        xe = jax.lax.all_to_all(xe, "model", split_axis=0,
                                concat_axis=1, tiled=True)
        f = layers.act_fn(s.act)                   # (E/tp, tp*C_l, d)
        g = jnp.einsum("ecd,edf->ecf", xe, wg)
        u = jnp.einsum("ecd,edf->ecf", xe, wu)
        ye = jnp.einsum("ecf,efd->ecd", f(g) * u, wo)
        # reverse a2a: outputs back to token owners
        ye = jax.lax.all_to_all(ye, "model", split_axis=1,
                                concat_axis=0, tiled=True)

        y = jnp.zeros((t_l + 1, d), x_l.dtype)
        y = y.at[slot_tok].add(ye * slot_gate[..., None], mode="drop")
        return y[:t_l].reshape(b_l, s_l, d), aux

    y, aux = run(x, params["router"], params["wi_gate"],
                 params["wi_up"], params["wo"])
    if s.n_shared:
        y = y + mlp(params["shared"], x, act=s.act)
    return constrain(y, "batch", "res_seq", "act_embed"), aux


def moe(params, x: jnp.ndarray, s: MoESpec):
    """x: (B, S, d) -> (y (B, S, d), aux_loss scalar).  Dispatches to
    the shard_map EP implementation unless configured (or forced by a
    missing mesh / non-divisible expert count) onto the SPMD gather."""
    if s.impl == "a2a":
        return moe_a2a(params, x, s)
    return _moe_gather(params, x, s)


def _moe_gather(params, x: jnp.ndarray, s: MoESpec):
    b, sq, d = x.shape
    t = b * sq
    xt = x.reshape(t, d)
    idx, gates, aux = router_probs(params, xt, s)      # (T, k)

    cap = s.capacity(t)
    # position of each (token, choice) within its expert, by arrival order
    flat_e = idx.reshape(-1)                           # (T*k,)
    one_hot = jax.nn.one_hot(flat_e, s.n_experts, dtype=jnp.int32)
    pos_in_e = jnp.cumsum(one_hot, axis=0) * one_hot   # (T*k, E)
    pos = jnp.sum(pos_in_e, axis=-1) - 1               # (T*k,)
    keep = pos < cap                                   # capacity drop

    # slot tables: which token fills (e, c); -1 = empty
    slot_tok = jnp.full((s.n_experts, cap), t, jnp.int32)  # t = pad row
    slot_gate = jnp.zeros((s.n_experts, cap), x.dtype)
    tok_ids = jnp.repeat(jnp.arange(t), s.top_k)
    e_ids = jnp.where(keep, flat_e, s.n_experts)       # drop -> pad expert
    c_ids = jnp.where(keep, pos, 0)
    slot_tok = slot_tok.at[(e_ids, c_ids)].set(
        jnp.where(keep, tok_ids, t), mode="drop")
    slot_gate = slot_gate.at[(e_ids, c_ids)].set(
        jnp.where(keep, gates.reshape(-1), 0.0), mode="drop")

    # gather tokens into expert slots: (E, C, d)
    xt_pad = jnp.concatenate([xt, jnp.zeros((1, d), xt.dtype)], axis=0)
    xe = xt_pad[slot_tok]
    xe = constrain(xe, "experts", "capacity", "act_embed")

    f = layers.act_fn(s.act)
    g = jnp.einsum("ecd,edf->ecf", xe, params["wi_gate"].astype(x.dtype))
    u = jnp.einsum("ecd,edf->ecf", xe, params["wi_up"].astype(x.dtype))
    h = constrain(f(g) * u, "experts", "capacity", "ffn")
    ye = jnp.einsum("ecf,efd->ecd", h, params["wo"].astype(x.dtype))
    ye = constrain(ye, "experts", "capacity", "act_embed")

    # combine: weighted scatter-add back to tokens
    y = jnp.zeros((t + 1, d), x.dtype)
    y = y.at[slot_tok].add(ye * slot_gate[..., None], mode="drop")
    y = y[:t].reshape(b, sq, d)

    if s.n_shared:
        y = y + mlp(params["shared"], x, act=s.act)
    return constrain(y, "batch", "res_seq", "act_embed"), aux
