"""Attention: GQA/MQA with RoPE, flash-chunked softmax, sliding window,
logit soft-capping, prefix-LM masks, and KV-cache prefill/decode.

TPU/memory design: full-sequence attention never materializes the
(S x S) score tensor — a ``lax.scan`` over KV chunks carries the running
(max, sum, acc) online-softmax state, bounding live memory to
(B, H, S, chunk) per layer (the jnp analog of flash attention; the
paper's line-buffer streaming applied to the sequence axis).

GQA-for-TP: when n_kv doesn't divide the model axis but n_heads does,
K/V heads are repeated to ``kv_eff`` (mathematically identical) so the
kv dim shards; see ModelConfig.kv_eff.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from repro.distributed.sharding import constrain
from repro.models import layers
from repro.models.params import P

NEG = -2.0e9


@dataclasses.dataclass(frozen=True)
class AttnSpec:
    d_model: int
    n_heads: int
    n_kv: int                     # logical kv heads (public config)
    kv_eff: int                   # kv heads after TP repetition
    head_dim: int
    rope_theta: float = 10000.0
    qkv_bias: bool = False
    query_scale: float = 1.0
    softcap: Optional[float] = None
    window: Optional[int] = None          # sliding-window size
    mask: str = "causal"                  # causal | full | prefix
    prefix_len: int = 0
    chunk: int = 1024

    @property
    def group(self) -> int:
        return self.n_heads // self.kv_eff


def schema(s: AttnSpec, cross: bool = False) -> dict:
    d, h, kv, hd = s.d_model, s.n_heads, s.n_kv, s.head_dim
    out = {
        "wq": P((d, h, hd), ("embed", "heads", "head_dim")),
        # K/V are stored at the LOGICAL kv-head count; repetition to
        # kv_eff happens in apply (keeps parameters faithful).
        "wk": P((d, kv, hd), ("embed", "kv_heads", "head_dim")),
        "wv": P((d, kv, hd), ("embed", "kv_heads", "head_dim")),
        "wo": P((h, hd, d), ("heads", "head_dim", "embed")),
    }
    if s.qkv_bias:
        out["bq"] = P((h, hd), ("heads", "head_dim"), init="zeros")
        out["bk"] = P((kv, hd), ("kv_heads", "head_dim"), init="zeros")
        out["bv"] = P((kv, hd), ("kv_heads", "head_dim"), init="zeros")
    return out


def _repeat_kv(x: jnp.ndarray, s: AttnSpec) -> jnp.ndarray:
    """(B, S, n_kv, D) -> (B, S, kv_eff, D) by head repetition."""
    if s.kv_eff == s.n_kv:
        return x
    r = s.kv_eff // s.n_kv
    return jnp.repeat(x, r, axis=2)


def qkv(params, x: jnp.ndarray, s: AttnSpec, positions, rope: bool = True):
    """x: (B, S, d) -> q (B, S, H, D), k/v (B, S, kv_eff, D), rope'd."""
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"].astype(x.dtype))
    k = jnp.einsum("bsd,dhk->bshk", x, params["wk"].astype(x.dtype))
    v = jnp.einsum("bsd,dhk->bshk", x, params["wv"].astype(x.dtype))
    if s.qkv_bias:
        q = q + params["bq"].astype(x.dtype)
        k = k + params["bk"].astype(x.dtype)
        v = v + params["bv"].astype(x.dtype)
    k = _repeat_kv(k, s)
    v = _repeat_kv(v, s)
    if rope:
        pos = positions
        q = layers.rope(q.swapaxes(1, 2), pos[:, None, :],
                        s.rope_theta).swapaxes(1, 2)
        k = layers.rope(k.swapaxes(1, 2), pos[:, None, :],
                        s.rope_theta).swapaxes(1, 2)
    q = constrain(q, "batch", "seq", "heads", "head_dim")
    k = constrain(k, "batch", "seq", "kv_heads", "head_dim")
    v = constrain(v, "batch", "seq", "kv_heads", "head_dim")
    return q, k, v


def _mask_block(s: AttnSpec, q_pos, k_pos, is_local):
    """(Sq, C) boolean mask for one KV chunk.  is_local: traced bool or
    None — selects the sliding window on alternating-stack local layers."""
    qp = q_pos[:, None]
    kp = k_pos[None, :]
    if s.mask == "full":
        base = jnp.ones((q_pos.shape[0], k_pos.shape[0]), bool)
    elif s.mask == "prefix":
        base = (kp <= qp) | (kp < s.prefix_len)
    else:
        base = kp <= qp
    if s.window is not None:
        win = base & (kp > qp - s.window)
        if is_local is None:
            base = win
        else:
            base = jnp.where(is_local, win, base)
    return base


def flash(q, k, v, s: AttnSpec, is_local=None):
    """Online-softmax attention over KV chunks.

    q: (B, Sq, H, D); k, v: (B, Skv, kv_eff, D).  Self-attention layout:
    q_pos == k_pos grids (offset 0).  Returns (B, Sq, H, D).
    """
    b, sq, h, d = q.shape
    skv = k.shape[1]
    g = s.group
    chunk = min(s.chunk, skv)
    if skv % chunk:                       # pad KV to a chunk multiple;
        pad = chunk - skv % chunk         # padded k_pos are masked below
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    nc = k.shape[1] // chunk

    qh = q.reshape(b, sq, s.kv_eff, g, d).transpose(0, 2, 3, 1, 4)
    kh = k.transpose(0, 2, 1, 3).reshape(b, s.kv_eff, nc, chunk, d)
    vh = v.transpose(0, 2, 1, 3).reshape(b, s.kv_eff, nc, chunk, d)
    kh = jnp.moveaxis(kh, 2, 0)         # (nc, B, kv, C, D)
    vh = jnp.moveaxis(vh, 2, 0)

    q_pos = jnp.arange(sq)
    scale = jnp.asarray(s.query_scale, jnp.float32)

    def body(carry, xs):
        m, l, acc = carry
        kc, vc, ci = xs
        k_pos = ci * chunk + jnp.arange(chunk)
        sc = jnp.einsum("bhgqd,bhkd->bhgqk", qh, kc,
                        preferred_element_type=jnp.float32) * scale
        sc = layers.softcap(sc, s.softcap)
        mask = _mask_block(s, q_pos, k_pos, is_local)
        mask = mask & (k_pos < skv)[None, :]          # KV padding
        sc = jnp.where(mask[None, None, None], sc, NEG)
        m_new = jnp.maximum(m, jnp.max(sc, axis=-1))
        p = jnp.exp(sc - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        # P in the KV dtype, f32 accumulate (never upcast the KV chunk:
        # XLA would hoist the convert and materialize an f32 cache)
        acc_new = (acc * corr[..., None]
                   + jnp.einsum("bhgqk,bhkd->bhgqd", p.astype(vc.dtype),
                                vc, preferred_element_type=jnp.float32))
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, s.kv_eff, g, sq), NEG, jnp.float32)
    l0 = jnp.zeros((b, s.kv_eff, g, sq), jnp.float32)
    a0 = jnp.zeros((b, s.kv_eff, g, sq, d), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0),
                                  (kh, vh, jnp.arange(nc)))
    out = acc / jnp.maximum(l, 1e-20)[..., None]
    out = out.transpose(0, 3, 1, 2, 4).reshape(b, sq, h, d)
    return constrain(out.astype(q.dtype), "batch", "seq", "heads",
                     "head_dim")


def project_out(params, o: jnp.ndarray, dtype) -> jnp.ndarray:
    y = jnp.einsum("bshk,hkd->bsd", o, params["wo"].astype(dtype))
    return constrain(y, "batch", "res_seq", "act_embed")


def full_layer(params, x, s: AttnSpec, positions, is_local=None,
               return_kv: bool = False):
    """Self-attention over a full sequence (train / prefill)."""
    q, k, v = qkv(params, x, s, positions)
    o = flash(q, k, v, s, is_local=is_local)
    y = project_out(params, o, x.dtype)
    if return_kv:
        # cache layout: (B, kv_eff, S, D)
        return y, (k.swapaxes(1, 2), v.swapaxes(1, 2))
    return y


def cross_layer(params, x, kv_cache, s: AttnSpec):
    """Cross-attention: q from x, K/V precomputed from the encoder
    (kv_cache = (k, v) each (B, kv_eff, S_src, D)); full mask."""
    b, sq, _ = x.shape
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"].astype(x.dtype))
    if s.qkv_bias:
        q = q + params["bq"].astype(x.dtype)
    k, v = kv_cache
    s_full = dataclasses.replace(s, mask="full", window=None)
    o = flash(q, k.swapaxes(1, 2), v.swapaxes(1, 2), s_full)
    return project_out(params, o, x.dtype)


def encode_kv(params, x_src, s: AttnSpec):
    """Precompute cross-attention K/V from encoder output (no rope)."""
    k = jnp.einsum("bsd,dhk->bshk", x_src, params["wk"].astype(x_src.dtype))
    v = jnp.einsum("bsd,dhk->bshk", x_src, params["wv"].astype(x_src.dtype))
    if s.qkv_bias:
        k = k + params["bk"].astype(x_src.dtype)
        v = v + params["bv"].astype(x_src.dtype)
    return (_repeat_kv(k, s).swapaxes(1, 2),
            _repeat_kv(v, s).swapaxes(1, 2))


def decode_qkv(params, x_tok, pos, s: AttnSpec):
    """Project one token.  Returns (q (B,1,H,D), k_tok/v_tok
    (B, kv_eff, 1, D)) — the caller writes k/v into the cache carry
    IN PLACE (single-slot write; the cache buffer is donated)."""
    b = x_tok.shape[0]
    pos_b = jnp.full((b, 1), pos, jnp.int32)
    q, k, v = qkv(params, x_tok, s, pos_b)
    return q, k.swapaxes(1, 2), v.swapaxes(1, 2)


def write_slot(cache, tok, slot, li=None):
    """Write one token into the cache at (layer li, position slot).

    cache: (L, B, H, Smax, D) with li, or (B, H, Smax, D) without.
    When the Smax dim is SHARDED, a plain dynamic-update-slice at a
    traced index makes the SPMD partitioner guard the write with a
    whole-buffer select per layer (full cache rewrite!); instead we
    shard_map the write so each shard updates at most its own slot in
    place — the flash-decode cache-write pattern.
    """
    from functools import partial

    from jax import shard_map
    from jax.sharding import PartitionSpec as Ps

    from repro.distributed.sharding import current_ctx, resolve

    tok = tok.astype(cache.dtype)
    seq_dim = cache.ndim - 2
    idx_prefix = (li,) if li is not None else ()
    tok_full = tok if li is None else tok[None]     # match cache rank

    def plain(c, t):
        idx = idx_prefix + (0,) * (c.ndim - 2 - len(idx_prefix)) \
            + (slot, 0)
        return jax.lax.dynamic_update_slice(c, t, idx)

    ctx = current_ctx()
    if ctx is None:
        return plain(cache, tok_full)
    axes = ("layers",) * (cache.ndim - 4) + (
        "batch", "kv_heads", "cache_seq", "head_dim")
    spec = resolve(ctx.rules.acts, axes, cache.shape, ctx.mesh)
    seq_sh = spec[seq_dim] if len(spec) > seq_dim else None
    if seq_sh is None:
        return plain(cache, tok_full)
    mesh_axes = (seq_sh,) if isinstance(seq_sh, str) else tuple(seq_sh)
    sizes = dict(ctx.mesh.shape)
    n_shards = 1
    for a in mesh_axes:
        n_shards *= sizes[a]
    shard_len = cache.shape[seq_dim] // n_shards
    tok_axes = ("batch", "kv_heads", None, "head_dim")
    tok_exp = tok if li is None else tok[None]
    tok_spec = resolve(ctx.rules.acts,
                       (("layers",) if li is not None else ())
                       + tok_axes, tok_exp.shape, ctx.mesh)

    # traced scalars (slot, li) enter as explicit replicated args
    li_arr = jnp.asarray(0 if li is None else li, jnp.int32)
    slot_arr = jnp.asarray(slot, jnp.int32)

    @partial(shard_map, mesh=ctx.mesh,
             in_specs=(spec, tok_spec, Ps(), Ps()),
             out_specs=spec, check_vma=False)
    def write(c_loc, t_loc, slot_, li_):
        sid = 0
        for a in mesh_axes:
            sid = sid * sizes[a] + jax.lax.axis_index(a)
        start = sid * shard_len
        loc = slot_ - start
        ok = (loc >= 0) & (loc < shard_len)
        loc_c = jnp.clip(loc, 0, shard_len - 1)
        pre = (li_,) if li is not None else ()
        idx = pre + (0,) * (c_loc.ndim - 2 - len(pre)) + (loc_c, 0)
        cur = jax.lax.dynamic_slice(c_loc, idx, t_loc.shape)
        upd = jnp.where(ok, t_loc, cur)
        return jax.lax.dynamic_update_slice(c_loc, upd, idx)

    return write(cache, tok_exp, slot_arr, li_arr)


def decode_attend(params, q, cache_k, cache_v, pos, s: AttnSpec,
                  is_local=None, rolling: bool = False):
    """Attend one query over the (already updated) cache slice.

    q: (B, 1, H, D); cache_k/v: (B, kv_eff, Smax, D); pos: tokens
    already in cache (the new token sits at slot pos / pos % Smax)."""
    b = q.shape[0]
    smax = cache_k.shape[2]
    qh = q.reshape(b, 1, s.kv_eff, s.group, -1).transpose(0, 2, 3, 1, 4)
    sc = jnp.einsum("bhgqd,bhkd->bhgqk", qh, cache_k,
                    preferred_element_type=jnp.float32) * s.query_scale
    sc = layers.softcap(sc, s.softcap)
    slots = jnp.arange(smax)
    if rolling:
        valid = (slots <= pos) | (pos >= smax)      # filled slots
    else:
        valid = slots <= pos
        if s.window is not None:
            win = valid & (slots > pos - s.window)
            valid = win if is_local is None else jnp.where(
                is_local, win, valid)
    sc = jnp.where(valid[None, None, None, None, :], sc, NEG)
    p = jax.nn.softmax(sc, axis=-1)
    # P in the cache dtype (never upcast the cache), f32 accumulate
    o = jnp.einsum("bhgqk,bhkd->bhgqd", p.astype(cache_v.dtype),
                   cache_v, preferred_element_type=jnp.float32)
    o = o.transpose(0, 3, 1, 2, 4).reshape(b, 1, s.n_heads, -1)
    return project_out(params, o.astype(q.dtype), q.dtype)
