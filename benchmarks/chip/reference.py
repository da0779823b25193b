"""Plain reference of the quad-camera frame path, kept with the benchmark.

The paper's datapath (arXiv 2104.00192, Sec. II-III) written as
straightforward ``jax.numpy`` over whole images: a 2-level bilinear
pyramid held in uint8, FAST-9/16 with 3x3 non-maximum suppression, the
7x7 integer Gaussian, the K strongest corners per level (score
descending, lower flat index first), intensity-centroid orientation,
rBRIEF steered through a 12-bin lookup table, stereo matching by
Hamming argmin inside the epipolar band, SAD rectification, depth, and
for a localized rig the rig-frame points, temporal matching and a
robust weighted Procrustes solve.

It imports nothing of the system under test: every constant, the
sampling pattern and each arithmetic rule is restated here, so a change
to the program cannot move the yardstick.  Outputs are nested dicts
with the field names of the program's output pytrees.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

# FAST-9/16: the 16 taps of the radius-3 Bresenham circle, (dx, dy).
CIRCLE16 = (
    (0, -3), (1, -3), (2, -2), (3, -1), (3, 0), (3, 1), (2, 2), (1, 3),
    (0, 3), (-1, 3), (-2, 2), (-3, 1), (-3, 0), (-3, -1), (-2, -2), (-1, -3),
)
ARC_LEN = 9
GAUSS7 = (1, 4, 8, 10, 8, 4, 1)             # integer taps, sum 36
PATCH_RADIUS = 15                           # 31 x 31 patches
PATCH = 2 * PATCH_RADIUS + 1
N_BITS = 256
N_ANGLE_BINS = 12
NO_CANDIDATE = 1 << 20                      # Hamming distance with no candidate
MIN_DISPARITY = 0.5


# --------------------------------------------------------------------------
# rBRIEF sampling pattern and its steering table (paper Sec. III-C)

def _pattern(seed: int = 20210606, radius: int = 13) -> np.ndarray:
    """(256, 4) int32 (ax, ay, bx, by): Gaussian offsets, sigma 7.5,
    rounded, kept inside ``radius`` with A != B."""
    rng = np.random.RandomState(seed)
    pts = []
    while len(pts) < N_BITS:
        cand = np.round(rng.normal(0.0, PATCH_RADIUS / 2.0,
                                   size=(4 * N_BITS, 4))).astype(np.int32)
        ok = (np.abs(cand[:, 0::2]).max(axis=1) ** 2
              + np.abs(cand[:, 1::2]).max(axis=1) ** 2) <= radius ** 2
        ok &= np.any(cand[:, :2] != cand[:, 2:], axis=1)
        pts.extend(cand[ok].tolist())
    return np.asarray(pts[:N_BITS], dtype=np.int32)


def _steer_table() -> np.ndarray:
    """(12, 256, 2) int32: for each 30-degree bin, the row-major 31x31
    patch index of the rotated A and B points."""
    pat = _pattern()
    rows = []
    for b in range(N_ANGLE_BINS):
        th = b * (2.0 * np.pi / N_ANGLE_BINS)
        rot = np.array([[np.cos(th), -np.sin(th)], [np.sin(th), np.cos(th)]])
        idx = []
        for pts in (pat[:, 0:2], pat[:, 2:4]):
            r = np.round(pts @ rot.T).astype(np.int32)
            idx.append((r[:, 1] + PATCH_RADIUS) * PATCH
                       + (r[:, 0] + PATCH_RADIUS))
        rows.append(np.stack(idx, axis=-1))
    return np.stack(rows).astype(np.int32)


STEER = _steer_table()


# --------------------------------------------------------------------------
# Dense stage: pyramid, blur, FAST, NMS

def level_shape(cfg: dict, level: int) -> tuple[int, int]:
    h, w = cfg["height"], cfg["width"]
    for _ in range(level):
        h = int(round(h / cfg["scale_factor"]))
        w = int(round(w / cfg["scale_factor"]))
    return h, w


def features_per_level(cfg: dict) -> list[int]:
    """The K budget split over levels in proportion to their area; the
    remainder goes to level 0."""
    areas = [np.prod(level_shape(cfg, l)) for l in range(cfg["n_levels"])]
    ks = [max(1, int(cfg["max_features"] * a / sum(areas))) for a in areas]
    ks[0] += cfg["max_features"] - sum(ks)
    return ks


def pyramid(image: jnp.ndarray, cfg: dict) -> list[jnp.ndarray]:
    """uint8 (H, W) -> uint8 levels; each level is the bilinear resize
    of the one above, rounded and clipped back to 8 bits."""
    levels = [image.astype(jnp.uint8)]
    for lvl in range(1, cfg["n_levels"]):
        out = jax.image.resize(levels[-1].astype(jnp.float32),
                               level_shape(cfg, lvl), method="bilinear")
        levels.append(jnp.round(jnp.clip(out, 0.0, 255.0)).astype(jnp.uint8))
    return levels


def _shift(pad, dy, dx, h, w, r):
    return jax.lax.dynamic_slice(pad, (r + dy, r + dx), (h, w))


def gaussian_blur(img: jnp.ndarray) -> jnp.ndarray:
    """7x7 separable integer Gaussian, edge padded, round half up."""
    h, w = img.shape
    pad = jnp.pad(img.astype(jnp.int32), 3, mode="edge")
    horiz = sum(t * jax.lax.dynamic_slice(pad, (0, k), (h + 6, w))
                for k, t in enumerate(GAUSS7))
    vert = sum(t * jax.lax.dynamic_slice(horiz, (k, 0), (h, w))
               for k, t in enumerate(GAUSS7))
    return ((vert + 648) // 1296).astype(jnp.uint8)


def fast_score(img: jnp.ndarray, threshold: float) -> jnp.ndarray:
    """FAST-9/16 score: the largest k such that 9 contiguous taps are all
    brighter (or all darker) than the centre by k; 0 unless above the
    threshold.  int16."""
    h, w = img.shape
    x = img.astype(jnp.int32)
    pad = jnp.pad(x, 3, mode="edge")
    d = jnp.stack([_shift(pad, dy, dx, h, w, 3) - x for dx, dy in CIRCLE16])
    dd = jnp.concatenate([d, d[:ARC_LEN - 1]], axis=0)
    bright = jnp.max(jnp.stack([jnp.min(dd[s:s + ARC_LEN], axis=0)
                                for s in range(16)]), axis=0)
    dark = jnp.min(jnp.stack([jnp.max(dd[s:s + ARC_LEN], axis=0)
                              for s in range(16)]), axis=0)
    score = jnp.maximum(bright, -dark)
    thr = int(np.floor(threshold))
    return jnp.where(score > thr, score, 0).astype(jnp.int16)


def nms3(score: jnp.ndarray) -> jnp.ndarray:
    """Keep a positive score that is >= each of its 8 neighbours
    (neighbours outside the image count as -1)."""
    h, w = score.shape
    pad = jnp.pad(score, 1, constant_values=jnp.asarray(-1, score.dtype))
    nmax = functools.reduce(jnp.maximum, [
        _shift(pad, dy, dx, h, w, 1)
        for dy in (-1, 0, 1) for dx in (-1, 0, 1) if (dy, dx) != (0, 0)])
    keep = jnp.where(score >= nmax, score, jnp.zeros_like(score))
    return keep * (score > 0).astype(score.dtype)


def top_k(score: jnp.ndarray, k: int, border: int):
    """The K strongest corners away from the border: a full two-key sort,
    score descending and flat index ascending.  Returns (xy (K, 2) int32,
    score (K,), valid (K,))."""
    h, w = score.shape
    row = jnp.arange(h)[:, None]
    col = jnp.arange(w)[None, :]
    inside = ((row >= border) & (row < h - border)
              & (col >= border) & (col < w - border))
    flat = jnp.where(inside, score, jnp.zeros_like(score)).reshape(-1)
    idx = jnp.arange(flat.shape[0], dtype=jnp.int32)
    neg, idx = jax.lax.sort((-flat, idx), num_keys=2)
    vals, idx = -neg[:k], idx[:k]
    return jnp.stack([idx % w, idx // w], axis=-1), vals, vals > 0


# --------------------------------------------------------------------------
# Sparse stage: orientation and steered rBRIEF

def patches(img: jnp.ndarray, xy: jnp.ndarray) -> jnp.ndarray:
    """(K, 31, 31) patches centred on xy (clamped into the image), edge
    padded, in the image's dtype."""
    h, w = img.shape
    pad = jnp.pad(img, PATCH_RADIUS, mode="edge")

    def one(p):
        x = jnp.clip(p[0], 0, w - 1)
        y = jnp.clip(p[1], 0, h - 1)
        return jax.lax.dynamic_slice(pad, (y, x), (PATCH, PATCH))

    return jax.vmap(one)(xy)


def orientation(raw_patches: jnp.ndarray) -> jnp.ndarray:
    """theta = atan2(m01, m10) over the radius-15 disc (paper Eq. 1)."""
    yy, xx = np.mgrid[-PATCH_RADIUS:PATCH_RADIUS + 1,
                      -PATCH_RADIUS:PATCH_RADIUS + 1]
    disc = (xx * xx + yy * yy) <= PATCH_RADIUS * PATCH_RADIUS
    xg = jnp.asarray(xx * disc, jnp.int32)
    yg = jnp.asarray(yy * disc, jnp.int32)
    p = raw_patches.astype(jnp.int32)
    m10 = jnp.sum(p * xg, axis=(-2, -1))
    m01 = jnp.sum(p * yg, axis=(-2, -1))
    return jnp.arctan2(m01.astype(jnp.float32), m10.astype(jnp.float32))


def descriptor(smooth_patches: jnp.ndarray, theta: jnp.ndarray):
    """(K, 8) uint32: bit i of word i // 32 is p(A_i) < p(B_i) under the
    steering of theta's nearest 30-degree bin."""
    b = jnp.mod(jnp.round(theta * np.float32(N_ANGLE_BINS / (2 * np.pi)))
                .astype(jnp.int32), N_ANGLE_BINS)
    idx = jnp.asarray(STEER)[b]
    flat = smooth_patches.reshape(-1, PATCH * PATCH)
    pa = jnp.take_along_axis(flat, idx[..., 0], axis=1)
    pb = jnp.take_along_axis(flat, idx[..., 1], axis=1)
    bits = (pa < pb).astype(jnp.uint32).reshape(-1, 8, 32)
    return jnp.sum(bits << jnp.arange(32, dtype=jnp.uint32), axis=-1,
                   dtype=jnp.uint32)


def features(image: jnp.ndarray, cfg: dict) -> dict:
    """One camera: uint8 (H, W) -> the K features of all levels."""
    parts = []
    for lvl, (img, k) in enumerate(zip(pyramid(image, cfg),
                                       features_per_level(cfg))):
        blur = gaussian_blur(img)
        score = fast_score(img, cfg["fast_threshold"])
        if cfg["nms"]:
            score = nms3(score)
        xy, vals, valid = top_k(score, k, cfg["border"])
        theta = orientation(patches(img, xy))
        parts.append(dict(
            xy=xy.astype(jnp.float32) * cfg["scale_factor"] ** lvl,
            level=jnp.full((k,), lvl, jnp.int32),
            score=vals.astype(jnp.float32), theta=theta,
            desc=descriptor(patches(blur, xy), theta), valid=valid))
    return {f: jnp.concatenate([p[f] for p in parts]) for f in parts[0]}


# --------------------------------------------------------------------------
# Stereo matching, SAD rectification and depth (paper Sec. II-C, III-D)

def _popcount(x: jnp.ndarray) -> jnp.ndarray:
    x = x - ((x >> 1) & jnp.uint32(0x55555555))
    x = (x & jnp.uint32(0x33333333)) + ((x >> 2) & jnp.uint32(0x33333333))
    x = (x + (x >> 4)) & jnp.uint32(0x0F0F0F0F)
    return ((x * jnp.uint32(0x01010101)) >> 24).astype(jnp.int32)


def meta(f: dict) -> jnp.ndarray:
    """(K, 4) rows of (x, y, level, valid) as float32."""
    return jnp.stack([f["xy"][..., 0], f["xy"][..., 1],
                      f["level"].astype(jnp.float32),
                      f["valid"].astype(jnp.float32)], axis=-1)


def hamming_argmin(desc_a, meta_a, desc_b, meta_b, band, max_dx):
    """Best right candidate per left feature inside the search region
    (|dy| <= band, 0 <= dx <= max_dx, same level, both valid), lowest
    index on ties.  (dist, idx); NO_CANDIDATE / -1 where none."""
    dist = jnp.sum(_popcount(desc_a[:, None, :] ^ desc_b[None, :, :]), -1)
    dx = meta_a[:, 0][:, None] - meta_b[:, 0][None, :]
    dy = jnp.abs(meta_a[:, 1][:, None] - meta_b[:, 1][None, :])
    ok = ((dy <= band) & (dx >= 0.0) & (dx <= max_dx)
          & (meta_a[:, 2][:, None] == meta_b[:, 2][None, :])
          & (meta_a[:, 3][:, None] > 0.5) & (meta_b[:, 3][None, :] > 0.5))
    dist = jnp.where(ok, dist, NO_CANDIDATE)
    best = jnp.min(dist, axis=1)
    idx = jnp.where(best >= NO_CANDIDATE, -1,
                    jnp.argmin(dist, axis=1).astype(jnp.int32))
    return best.astype(jnp.int32), idx


def window(img: jnp.ndarray, xy: jnp.ndarray, ph: int, pw: int):
    """(K, ph, pw) windows centred at round(xy), clamped, edge padded."""
    ry, rx = ph // 2, pw // 2
    pad = jnp.pad(img.astype(jnp.float32), ((ry, ry), (rx, rx)), mode="edge")
    xs = jnp.clip(jnp.round(xy[:, 0]).astype(jnp.int32), 0, img.shape[1] - 1)
    ys = jnp.clip(jnp.round(xy[:, 1]).astype(jnp.int32), 0, img.shape[0] - 1)
    return jax.vmap(lambda x, y: jax.lax.dynamic_slice(pad, (y, x), (ph, pw))
                    )(xs, ys)


def stereo_pair(img_l, img_r, fl: dict, fr: dict, cfg: dict, fx_baseline):
    """Matches and depth of one stereo pair."""
    ml, mr = meta(fl), meta(fr)
    dist, idx = hamming_argmin(fl["desc"], ml, fr["desc"], mr,
                               float(cfg["row_band"]),
                               float(cfg["max_disparity"]))
    valid = (idx >= 0) & (dist <= cfg["max_hamming"]) & fl["valid"]
    eff = jnp.where(valid, idx, 0)
    rxy = mr[eff, :2]
    p, r = cfg["sad_window"], cfg["sad_range"]
    lp = window(img_l, ml[:, :2], p, p).astype(jnp.int32)
    rs = window(img_r, rxy, p, p + 2 * r).astype(jnp.int32)
    sad = jnp.stack([jnp.sum(jnp.abs(lp - rs[:, :, s:s + p]), axis=(1, 2))
                     for s in range(2 * r + 1)], axis=1)
    x_r = rxy[:, 0] + (jnp.argmin(sad, axis=1).astype(jnp.float32)
                       - float(r))
    disparity = fl["xy"][:, 0] - x_r
    ok = valid & (disparity > MIN_DISPARITY)
    depth = jnp.where(ok, fx_baseline
                      / jnp.maximum(disparity, MIN_DISPARITY), 0.0)
    return (dict(right_index=eff, distance=dist, valid=valid),
            dict(disparity=jnp.where(ok, disparity, 0.0), depth=depth,
                 xy_right=jnp.stack([x_r, rxy[:, 1]], axis=-1), valid=ok))


def rig_frame(images: jnp.ndarray, cfg: dict, rig: dict) -> dict:
    """(n_cameras, H, W) uint8 -> the stereo output of one rig frame,
    leading (n_pairs,) axes."""
    feats = [features(images[c], cfg) for c in range(images.shape[0])]
    pairs = [stereo_pair(images[l], images[r], feats[l], feats[r], cfg,
                         rig["fx"] * rig["baseline"])
             for l, r in rig["pairs"]]
    stack = lambda xs: jax.tree.map(lambda *a: jnp.stack(a), *xs)  # noqa: E731
    return dict(features_l=stack([feats[l] for l, _ in rig["pairs"]]),
                features_r=stack([feats[r] for _, r in rig["pairs"]]),
                matches=stack([m for m, _ in pairs]),
                depth=stack([d for _, d in pairs]))


# --------------------------------------------------------------------------
# Localization backend: rig-frame points, temporal matching, pose

def rig_points(stereo: dict, rig: dict) -> jnp.ndarray:
    """(n_pairs, K, 3) rig-frame points of the left features: pinhole
    back-projection, then each pair's camera->rig rotation."""
    xy, z = stereo["features_l"]["xy"], stereo["depth"]["depth"]
    x = (xy[..., 0] - rig["cx"]) / rig["fx"] * z
    y = (xy[..., 1] - rig["cy"]) / rig["fy"] * z
    cam = jnp.stack([x, y, z], axis=-1)
    rot = jnp.asarray(rig["pair_rotations"], jnp.float32)
    return jnp.einsum("pji,...pki->...pkj", rot, cam[None],
                      precision=jax.lax.Precision.HIGHEST)[0]


def state(stereo: dict, points: jnp.ndarray) -> dict:
    fl = stereo["features_l"]
    return dict(desc=fl["desc"], meta=meta(fl), points=points,
                valid=fl["valid"] & stereo["depth"]["valid"])


def zero_state(n_pairs: int, k: int) -> dict:
    return dict(desc=jnp.zeros((n_pairs, k, 8), jnp.uint32),
                meta=jnp.zeros((n_pairs, k, 4), jnp.float32),
                points=jnp.zeros((n_pairs, k, 3), jnp.float32),
                valid=jnp.zeros((n_pairs, k), bool))


def _mm(a, b):
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


def kabsch(a, b, w):
    """Weighted rigid alignment minimising sum w |R a + t - b|^2."""
    w = w / jnp.maximum(jnp.sum(w), 1e-6)
    ca = jnp.sum(w[:, None] * a, axis=0)
    cb = jnp.sum(w[:, None] * b, axis=0)
    h = _mm((w[:, None] * (a - ca)).T, b - cb)
    u, _, vt = jnp.linalg.svd(h)
    d = jnp.sign(jnp.linalg.det(_mm(vt.T, u.T)))
    s = jnp.diag(jnp.asarray([1.0, 1.0, 1.0])).at[2, 2].set(d)
    r = _mm(_mm(vt.T, s), u.T)
    return r, cb - _mm(r, ca)


def solve_pose(a, b, w0, iters=3, keep_frac=0.7, min_corr=3):
    """Robust Procrustes: re-solve keeping the keep_frac smallest
    residuals of the support, `iters` rounds; identity and invalid when
    degenerate."""
    w0 = jnp.where(jnp.isfinite(a).all(-1) & jnp.isfinite(b).all(-1),
                   w0, 0.0)
    n0 = jnp.sum((w0 > 0).astype(jnp.int32))

    def round_(w, _):
        r, t = kabsch(a, b, w)
        res = jnp.linalg.norm(_mm(a, r.T) + t - b, axis=-1)
        n = jnp.sum((w > 0).astype(jnp.int32))
        keep = jnp.maximum(jnp.int32(min_corr),
                           jnp.ceil(keep_frac * n).astype(jnp.int32))
        thr = jnp.sort(jnp.where(w > 0, res, jnp.inf))[
            jnp.clip(keep - 1, 0, w.shape[0] - 1)]
        return jnp.where((res <= thr) & (w0 > 0), w0, 0.0), None

    w, _ = jax.lax.scan(round_, w0, None, length=iters)
    r, t = kabsch(a, b, w)
    inliers = jnp.sum((w > 0).astype(jnp.int32))
    wn = w / jnp.maximum(jnp.sum(w), 1e-6)
    c = a - jnp.sum(wn[:, None] * a, axis=0)
    spread = jnp.sum(wn * jnp.sum(c * c, axis=-1))
    ok = ((inliers >= min_corr) & (n0 >= min_corr) & (spread > 1e-8)
          & jnp.isfinite(r).all() & jnp.isfinite(t).all())
    return dict(rotation=jnp.where(ok, r, jnp.eye(3)).astype(jnp.float32),
                translation=jnp.where(ok, t, 0.0).astype(jnp.float32),
                inliers=inliers, valid=ok)


def localize(stereo: dict, prev: dict, cfg: dict, rig: dict):
    """Points and the pose since `prev` for one rig frame; returns
    (output dict, state for the next frame)."""
    pts = rig_points(stereo, rig)
    curr = state(stereo, pts)
    rx = float(cfg["temporal_radius"])
    meta_a = prev["meta"].at[..., 0].add(rx)
    dist, idx = jax.vmap(lambda da, ma, db, mb: hamming_argmin(
        da, ma, db, mb, rx, 2.0 * rx))(prev["desc"], meta_a, curr["desc"],
                                      curr["meta"])
    ok = (idx >= 0) & (dist <= cfg["max_hamming"]) & (prev["meta"][..., 3] > 0.5)
    eff = jnp.where(ok, idx, 0)
    pts_curr = jnp.take_along_axis(curr["points"], eff[..., None], axis=-2)
    ok_curr = jnp.take_along_axis(curr["valid"], eff, axis=-1)
    w = (ok & prev["valid"] & ok_curr).astype(jnp.float32)
    n = prev["points"].shape[0] * prev["points"].shape[1]
    pose = jax.vmap(solve_pose)(prev["points"].reshape(1, n, 3),
                                pts_curr.reshape(1, n, 3), w.reshape(1, n))
    pose = jax.tree.map(lambda x: x[0], pose)
    return dict(stereo=stereo, points=pts, pose=pose), curr


# --------------------------------------------------------------------------
# Trajectory error against the scene's ground truth

def trajectory_error(rotations, translations, gt_rot, gt_pos) -> dict:
    """ATE (m), RPE translation RMSE (m) and RPE rotation mean (deg) of a
    relative-pose sequence (row t: frame t-1 -> t; row 0 ignored) against
    ground-truth rig rotations (T, 3, 3, rig->world) and positions (T, 3)."""
    rot = np.asarray(rotations, np.float64)
    tr = np.asarray(translations, np.float64)
    gt_rot = np.asarray(gt_rot, np.float64)
    gt_pos = np.asarray(gt_pos, np.float64)
    n = rot.shape[0]
    est = np.zeros((n, 3))
    r_w = np.eye(3)
    for t in range(1, n):
        r_w = r_w @ rot[t].T
        est[t] = est[t - 1] - r_w @ tr[t]
    ref = np.stack([gt_rot[0].T @ (p - gt_pos[0]) for p in gt_pos])
    ate = float(np.sqrt(np.mean(np.sum((est - ref) ** 2, axis=-1))))
    d_t, d_r = [], []
    for t in range(1, n):
        g_rot = gt_rot[t].T @ gt_rot[t - 1]
        g_tr = gt_rot[t].T @ (gt_pos[t - 1] - gt_pos[t])
        d_t.append(np.sum((tr[t] - g_tr) ** 2))
        c = np.clip((np.trace(rot[t] @ g_rot.T) - 1.0) / 2.0, -1.0, 1.0)
        d_r.append(np.degrees(np.arccos(c)))
    return dict(ate_m=ate, rpe_t_m=float(np.sqrt(np.mean(d_t))),
                rpe_r_deg=float(np.mean(d_r)))
