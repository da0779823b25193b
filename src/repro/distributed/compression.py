"""8-bit wire formats: compressed gradient all-reduce + feature wire.

The TPU analog of the paper's 8-bit word-length optimization, applied
everywhere data crosses a link:

1. GRADIENT SYNC (``compressed_psum``): a ring reduce-scatter whose
   wire format is int8 with one f32 scale per shard-chunk, followed by
   an int8 all-gather.  Wire volume: 2 x size/4 bytes vs 2 x size
   (f32 AR) — ~4x reduction, at a bounded quantization error (tested).

   Accumulation stays exact-ish: each hop dequantizes, adds in f32, and
   requantizes, so error grows O(log-ish) with ring length rather than
   compounding catastrophically; relative error is bounded by ~1/127
   per hop on the running partial sum.

2. FEATURE / MATCH WIRE (``encode_features`` et al.): the serving tier
   ships frontend outputs off-accelerator (VO backend, fleet uplink).
   Descriptors are BIT PATTERNS, not magnitudes — they go over the wire
   as a lossless uint32 <-> 4-byte little-endian view (256 bits stay
   256 bits, Hamming distances unchanged); float fields (disparity,
   depth, coordinates) reuse the SAME int8+scale quantizer as the
   gradient ring (bounded relative error ~1/127 of the field's max);
   validity masks pack to one bit per entry; match distances fit uint16
   with a no-match sentinel.  Round-trip pins live in
   tests/test_precision.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as Ps

from repro.core.types import DepthSet, FeatureSet, MatchSet, PoseSet


def _quant(x: jnp.ndarray):
    scale = jnp.max(jnp.abs(x)) / 127.0 + 1e-30
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale


def _dequant(q: jnp.ndarray, scale: jnp.ndarray) -> jnp.ndarray:
    return q.astype(jnp.float32) * scale


def _ring_allreduce_int8(x: jnp.ndarray, axis: str, n: int) -> jnp.ndarray:
    """All-reduce over mesh axis `axis` (static size ``n`` — the caller
    reads it off the mesh; jax<0.5 has no ``lax.axis_size``) with int8
    wire format.

    x: per-device f32 vector (flat, length % n == 0; caller pads).
    Classic two-phase ring: n-1 reduce-scatter hops + n-1 all-gather
    hops, each hop sending size/n int8 + one f32 scale.
    """
    me = jax.lax.axis_index(axis)
    chunks = x.reshape(n, -1)
    perm = [(i, (i + 1) % n) for i in range(n)]

    # reduce-scatter: after n-1 hops, device d owns the full sum of
    # chunk (d + 1) % n
    def rs_body(i, carry):
        acc = carry                       # (n, c) running per-chunk sums
        send_idx = (me - i) % n
        q, s = _quant(acc[send_idx])
        q2 = jax.lax.ppermute(q, axis, perm)
        s2 = jax.lax.ppermute(s, axis, perm)
        recv_idx = (me - i - 1) % n
        acc = acc.at[recv_idx].add(_dequant(q2, s2))
        return acc

    acc = jax.lax.fori_loop(0, n - 1, rs_body, chunks)
    own = (me + 1) % n                    # chunk this device fully owns

    # all-gather: circulate the owned chunk in int8
    out = jnp.zeros_like(chunks)
    q, s = _quant(acc[own])
    out = out.at[own].set(_dequant(q, s))

    def ag_body(i, carry):
        out_c, q_c, s_c = carry
        q2 = jax.lax.ppermute(q_c, axis, perm)
        s2 = jax.lax.ppermute(s_c, axis, perm)
        idx = (me - i) % n                # chunk that just arrived
        out_c = out_c.at[idx].set(_dequant(q2, s2))
        return out_c, q2, s2

    out, _, _ = jax.lax.fori_loop(0, n - 1, ag_body, (out, q, s))
    return out.reshape(x.shape)


def compressed_psum(tree, mesh: Mesh, axis: str = "data"):
    """Compressed all-reduce (sum) of a pytree of replicated-along-axis
    f32 arrays.  Returns the summed tree.  Used by the compressed train
    step to sync per-shard gradients over the DP axis."""
    flat, treedef = jax.tree.flatten(tree)
    sizes = [x.size for x in flat]
    n = mesh.shape[axis]
    cat = jnp.concatenate([x.reshape(-1) for x in flat])
    pad = (-cat.size) % n
    cat = jnp.pad(cat, (0, pad))

    spec = Ps(*(None,) * cat.ndim)

    @functools.partial(shard_map, mesh=mesh, in_specs=spec,
                       out_specs=spec, check_vma=False)
    def run(v):
        return _ring_allreduce_int8(v, axis, n)

    summed = run(cat)[:cat.size - pad if pad else None]
    if pad:
        summed = summed[:sum(sizes)]
    out, off = [], 0
    for x, size in zip(flat, sizes):
        out.append(summed[off:off + size].reshape(x.shape))
        off += size
    return jax.tree.unflatten(treedef, out)


# ---------------------------------------------------------------------------
# Feature / match wire format (int8 + scale, lossless descriptor bytes)
# ---------------------------------------------------------------------------

#: uint16 sentinel for "no match" slots (right_index == -1 or distance
#: >= the kernels' MATCH_BIG).  Real Hamming distances are <= 256 and
#: real indices are < max_features (<= 1000), so the sentinel is
#: unambiguous.
WIRE_NO_MATCH = 0xFFFF

_BYTE_SHIFTS = jnp.arange(4, dtype=jnp.uint32) * jnp.uint32(8)


def encode_descriptors(desc: jnp.ndarray) -> jnp.ndarray:
    """(..., 8) uint32 rBRIEF descriptors -> (..., 32) uint8 wire bytes
    (little-endian per word).  LOSSLESS: descriptors are bit patterns —
    quantizing them like magnitudes would corrupt Hamming distances, so
    the wire format is a pure byte view."""
    d = desc.astype(jnp.uint32)
    b = (d[..., None] >> _BYTE_SHIFTS) & jnp.uint32(0xFF)
    return b.astype(jnp.uint8).reshape(desc.shape[:-1] + (32,))


def decode_descriptors(wire: jnp.ndarray) -> jnp.ndarray:
    """Inverse of ``encode_descriptors``: (..., 32) uint8 -> (..., 8)
    uint32, bit-exact."""
    b = wire.astype(jnp.uint32).reshape(wire.shape[:-1] + (8, 4))
    return jnp.sum(b << _BYTE_SHIFTS, axis=-1, dtype=jnp.uint32)


def quantize_f32(x: jnp.ndarray):
    """Public int8+scale quantizer — the gradient ring's wire format
    reused for float feature fields.  Returns (int8 codes, f32 scale);
    absolute error is bounded by scale/2 ~= max|x| / 254."""
    return _quant(x)


def dequantize_f32(q: jnp.ndarray, scale: jnp.ndarray) -> jnp.ndarray:
    return _dequant(q, scale)


def _pack_mask(valid: jnp.ndarray) -> jnp.ndarray:
    flat = valid.reshape(-1).astype(jnp.uint8)
    pad = (-flat.size) % 8
    flat = jnp.pad(flat, (0, pad))
    bits = flat.reshape(-1, 8) << jnp.arange(8, dtype=jnp.uint8)
    return jnp.sum(bits, axis=-1, dtype=jnp.uint8)


def _unpack_mask(packed: jnp.ndarray, shape) -> jnp.ndarray:
    bits = (packed[:, None] >> jnp.arange(8, dtype=jnp.uint8)) & 1
    n = int(np.prod(shape))
    return bits.reshape(-1)[:n].reshape(shape).astype(bool)


def _encode_u16(x: jnp.ndarray, no_value) -> jnp.ndarray:
    """int32 field -> uint16 with WIRE_NO_MATCH for ``no_value`` slots
    (sentinel comparison is >= so the kernels' MATCH_BIG maps too)."""
    x = x.astype(jnp.int32)
    bad = (x < 0) | (x >= jnp.int32(no_value))
    return jnp.where(bad, jnp.int32(WIRE_NO_MATCH), x).astype(jnp.uint16)


def _decode_u16(w: jnp.ndarray, no_value) -> jnp.ndarray:
    x = w.astype(jnp.int32)
    return jnp.where(x == WIRE_NO_MATCH, jnp.int32(no_value), x)


def encode_features(feat: FeatureSet) -> dict:
    """FeatureSet -> wire dict.  Descriptors lossless (uint8 bytes);
    xy/score/theta int8+scale (bounded error); level uint8; valid
    packed bits.  ~37 bytes/feature vs ~57 f32 — and the descriptor,
    the dominant field, crosses at exactly 32 bytes either way."""
    qxy, sxy = _quant(feat.xy)
    qsc, ssc = _quant(feat.score)
    qth, sth = _quant(feat.theta)
    return dict(
        desc=encode_descriptors(feat.desc),
        xy=qxy, xy_scale=sxy, score=qsc, score_scale=ssc,
        theta=qth, theta_scale=sth,
        level=feat.level.astype(jnp.uint8),
        valid=_pack_mask(feat.valid), k=int(feat.valid.shape[-1]),
        shape=tuple(feat.valid.shape))


def decode_features(wire: dict) -> FeatureSet:
    shape = wire["shape"]
    return FeatureSet(
        xy=_dequant(wire["xy"], wire["xy_scale"]),
        level=wire["level"].astype(jnp.int32),
        score=_dequant(wire["score"], wire["score_scale"]),
        theta=_dequant(wire["theta"], wire["theta_scale"]),
        desc=decode_descriptors(wire["desc"]),
        valid=_unpack_mask(wire["valid"], shape))


def encode_matches(matches: MatchSet) -> dict:
    """MatchSet -> wire dict: uint16 index/distance with a no-match
    sentinel (LOSSLESS — both fields are small ints), packed validity.

    Raises eagerly when the feature budget is too large for the uint16
    sentinel scheme: with K >= WIRE_NO_MATCH a legitimate
    ``right_index`` value can equal (or exceed and silently map to) the
    0xFFFF no-match sentinel, corrupting matches on decode with no
    error anywhere — the failure the eager check converts into a named
    ValueError at encode time."""
    k = int(matches.right_index.shape[-1])
    if k >= WIRE_NO_MATCH:
        raise ValueError(
            f"encode_matches: matches.right_index has K = {k} "
            f">= WIRE_NO_MATCH (0x{WIRE_NO_MATCH:04X}) — a legitimate "
            "match index would collide with the uint16 no-match "
            "sentinel and decode as 'no match'.  Shrink "
            "ORBConfig.max_features or widen the wire index field "
            "before shipping this set.")
    return dict(
        right_index=_encode_u16(matches.right_index, WIRE_NO_MATCH),
        distance=_encode_u16(matches.distance, WIRE_NO_MATCH),
        valid=_pack_mask(matches.valid),
        shape=tuple(matches.valid.shape))


def decode_matches(wire: dict, *, no_match_distance: int) -> MatchSet:
    """``no_match_distance`` restores the kernels' BIG sentinel (pass
    ``ops.NO_MATCH_DIST``) so decoded sets compare equal upstream."""
    return MatchSet(
        right_index=_decode_u16(wire["right_index"], -1),
        distance=_decode_u16(wire["distance"], no_match_distance),
        valid=_unpack_mask(wire["valid"], wire["shape"]))


def encode_depth(depth: DepthSet) -> dict:
    """DepthSet -> wire dict: disparity/depth/xy_right int8+scale
    (bounded relative error ~1/127), packed validity."""
    qd, sd = _quant(depth.disparity)
    qz, sz = _quant(depth.depth)
    qxy, sxy = _quant(depth.xy_right)
    return dict(disparity=qd, disparity_scale=sd,
                depth=qz, depth_scale=sz,
                xy_right=qxy, xy_right_scale=sxy,
                valid=_pack_mask(depth.valid),
                shape=tuple(depth.valid.shape))


def decode_depth(wire: dict) -> DepthSet:
    return DepthSet(
        disparity=_dequant(wire["disparity"], wire["disparity_scale"]),
        depth=_dequant(wire["depth"], wire["depth_scale"]),
        xy_right=_dequant(wire["xy_right"], wire["xy_right_scale"]),
        valid=_unpack_mask(wire["valid"], wire["shape"]))


def encode_pose(pose: PoseSet) -> dict:
    """PoseSet -> wire dict, LOSSLESS (raw f32/i32 + packed validity).

    The pose is the backend's *product* — the thing the accuracy gates
    certify — so unlike the bulky int8 feature/depth payloads it ships
    verbatim: 9 + 3 floats and one int per rig is noise next to the
    descriptor slabs, and quantizing it would corrupt exactly the
    quantity the fleet operator consumes."""
    valid = jnp.atleast_1d(jnp.asarray(pose.valid, bool))
    return dict(rotation=jnp.asarray(pose.rotation, jnp.float32),
                translation=jnp.asarray(pose.translation, jnp.float32),
                inliers=jnp.asarray(pose.inliers, jnp.int32),
                valid=_pack_mask(valid),
                shape=tuple(np.shape(pose.valid)))


def decode_pose(wire: dict) -> PoseSet:
    return PoseSet(
        rotation=wire["rotation"], translation=wire["translation"],
        inliers=wire["inliers"],
        valid=_unpack_mask(wire["valid"], wire["shape"]))


def encode_points(points: jnp.ndarray) -> dict:
    """Rig-frame 3-D points -> wire dict, LOSSLESS raw f32.  Validity
    is NOT duplicated here: a point is usable iff the feature and depth
    masks already on the wire say so (``features_l.valid & depth.valid``
    — what ``localization.state_from`` reconstructs on the far side)."""
    return dict(points=jnp.asarray(points, jnp.float32),
                shape=tuple(np.shape(points)))


def decode_points(wire: dict) -> jnp.ndarray:
    return wire["points"]


def wire_bytes(wire) -> int:
    """Total payload bytes of a wire dict (or nest of them) — array
    itemsizes only; keys/shape metadata ride the header."""
    total = 0
    for v in jax.tree.leaves(wire):
        if hasattr(v, "size") and hasattr(v, "dtype"):
            total += int(v.size) * int(np.dtype(v.dtype).itemsize)
    return total
