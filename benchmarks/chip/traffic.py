"""The one traffic generator: rig frames rendered on the device from a seed.

A traffic mix (``traffic/<name>.json``) gives the scene and the ring of
frames each rig replays; a configuration gives the cameras.  The scene
is the constant-twist landmark field of the repo's localization gates:
400 textured landmarks in front of and behind the rig, 2-12 m deep, a
static noisy background per camera, the rig stepping (0.25, 0, 0.1) m a
frame.  Every frame is rendered in ONE jitted call and quantized to
uint8, as an 8-bit camera delivers it.  Landmarks, textures and noise
come from ``--seed``; the sizes, the ring and the trajectory do not, so
every seed asks the system for the same work.
"""

from __future__ import annotations

import functools
import typing

import jax
import jax.numpy as jnp
import numpy as np


class Frames(typing.NamedTuple):
    images: np.ndarray        # (T, n_cameras, H, W) uint8, host memory
    rig_rot: np.ndarray       # (T, 3, 3) rig -> world rotation
    rig_pos: np.ndarray       # (T, 3) rig position in the world


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative seed, also one above 2**32."""
    if seed < 0:
        raise ValueError(f"--seed must be >= 0, got {seed}")
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def camera_poses(rig_rot, rig_pos, pair_rotations, baseline):
    """World pose (rotation, position) of every camera: the left camera
    of pair p sits at the rig origin turned by the pair's rotation, the
    right one ``baseline`` along that camera's x axis."""
    rots, poss = [], []
    for r_pair in pair_rotations:
        for side in (0.0, 1.0):
            r_wc = rig_rot @ jnp.asarray(r_pair, jnp.float32)
            rots.append(r_wc)
            poss.append(rig_pos + r_wc @ jnp.asarray([side * baseline, 0.0,
                                                      0.0]))
    return jnp.stack(rots), jnp.stack(poss)


@functools.partial(jax.jit, static_argnames=("geom",))
def _render(key, rig_rot, rig_pos, geom):
    cam, scene, pair_rot = (dict(geom[0]), dict(geom[1]), geom[2])
    h, w = cam["height"], cam["width"]
    n, s = scene["n_points"], scene["stamp"]
    k_xyz, k_base, k_tex, k_bg = jax.random.split(key, 4)
    lo, hi = scene["depth_range"]
    sp = scene["spread"]
    x, y, z = jax.random.uniform(k_xyz, (3, n))
    z = lo + (hi - lo) * z
    pts = jnp.stack([sp * (2 * x - 1), sp / 2 * (2 * y - 1),
                     jnp.where(jnp.arange(n) < n // 2, z, -z)], axis=-1)
    base = jax.random.uniform(k_base, (n,), minval=90.0, maxval=250.0)
    tex = jax.random.uniform(k_tex, (n, s, s), minval=0.4, maxval=1.0)
    tex = (tex * base[:, None, None]).at[:, s // 2, s // 2].set(255.0)
    n_cam = 2 * len(pair_rot)
    yy, xx = jnp.mgrid[0:h, 0:w]
    grad = 40.0 + 30.0 * (xx / w) + 20.0 * (yy / h)
    bg = jnp.stack([jnp.clip(grad + scene["noise_std"] * jax.random.normal(
        jax.random.fold_in(k_bg, c), (h, w)), 0.0, 255.0)
        for c in range(n_cam)])
    r = s // 2
    dy, dx = jnp.mgrid[-r:r + 1, -r:r + 1]

    def view(r_wc, p_w, background):
        pc = (pts - p_w) @ r_wc
        vis = pc[:, 2] > 0.5
        zs = jnp.where(vis, pc[:, 2], 1.0)
        u = jnp.round(cam["fx"] * pc[:, 0] / zs + cam["cx"]).astype(jnp.int32)
        v = jnp.round(cam["fy"] * pc[:, 1] / zs + cam["cy"]).astype(jnp.int32)
        inb = vis & (u >= r) & (u < w - r) & (v >= r) & (v < h - r)
        rows = jnp.where(inb[:, None, None], v[:, None, None] + dy, h)
        cols = u[:, None, None] + dx
        img = background.at[rows, cols].max(tex, mode="drop")
        return jnp.round(jnp.clip(img, 0.0, 255.0)).astype(jnp.uint8)

    def frame(rr, rp):
        rots, poss = camera_poses(rr, rp, pair_rot, cam["baseline"])
        return jax.vmap(view)(rots, poss, bg)

    return jax.vmap(frame)(rig_rot, rig_pos)


def trajectory(n_frames: int, step: typing.Sequence[float]):
    """Constant translation, no turn: (rotations, positions) per frame."""
    rot = np.broadcast_to(np.eye(3), (n_frames, 3, 3)).copy()
    pos = np.arange(n_frames)[:, None] * np.asarray(step, np.float64)
    return rot, pos


def render(config: dict, scene: dict, n_frames: int, seed: int) -> Frames:
    """``n_frames`` consecutive rig frames of the scene seeded by
    ``seed``, rendered on the default device, returned in host memory."""
    rot, pos = trajectory(n_frames, scene["step"])
    cam = dict(config["camera"], height=config["orb"]["height"],
               width=config["orb"]["width"])
    geom = (tuple(sorted(cam.items())),
            tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                         for k, v in scene.items())),
            tuple(tuple(map(tuple, r)) for r in config["rig"]["pair_rotations"]))
    images = _render(seed_key(seed), jnp.asarray(rot, jnp.float32),
                     jnp.asarray(pos, jnp.float32), geom)
    return Frames(np.asarray(jax.device_get(images)), rot, pos)
