"""The comparison that decides ``correct``.

Every answer the window produced is held against the plain reference
(``reference.py``) of the same frame.  A field is compared exactly,
element by element, in the lanes its validity mask marks as real (the
masks themselves are compared in full): the frontend's keypoints,
scores, orientations, descriptors, matches, disparities and depths are
integer-valued or come from the same elementwise float operations, so
the kernels promise equality, not closeness.  Lanes that are not real
carry values no consumer may read, and a program may fill them as it
likes.
"""

from __future__ import annotations

import numpy as np

_FEATURES = ("xy", "level", "score", "theta", "desc")

#: (leaf, mask) pairs of one rig frame's stereo output; mask None = all.
STEREO = tuple(
    [(f"{side}.valid", None) for side in ("features_l", "features_r")]
    + [(f"{side}.{f}", f"{side}.valid")
       for side in ("features_l", "features_r") for f in _FEATURES]
    + [("matches.valid", None), ("matches.right_index", "matches.valid"),
       ("matches.distance", "matches.valid"),
       ("depth.valid", None), ("depth.disparity", "depth.valid"),
       ("depth.depth", "depth.valid"), ("depth.xy_right", "depth.valid")])

#: The same for a localized frame: the stereo output under ``stereo``,
#: then the rig-frame points of usable features and the pose.
LOCALIZED = tuple(
    [(f"stereo.{leaf}", None if mask is None else f"stereo.{mask}")
     for leaf, mask in STEREO]
    + [("points", "usable"), ("pose.valid", None),
       ("pose.inliers", "pose.valid"), ("pose.rotation", "pose.valid"),
       ("pose.translation", "pose.valid")])


def to_tree(x):
    """Program output (named tuples of arrays) -> nested dict of numpy."""
    if hasattr(x, "_asdict"):
        return {k: to_tree(v) for k, v in x._asdict().items()}
    if isinstance(x, dict):
        return {k: to_tree(v) for k, v in x.items()}
    return np.asarray(x)


def leaf(tree, path: str):
    for key in path.split("."):
        if not isinstance(tree, dict) or key not in tree:
            return None
        tree = tree[key]
    return tree


def _mask(want: dict, path: str | None):
    if path is None:
        return None
    if path == "usable":
        st = want["stereo"]
        return st["features_l"]["valid"] & st["depth"]["valid"]
    return leaf(want, path)


def frame_mismatches(got: dict, want: dict, rules) -> dict:
    """Leaf -> number of compared elements that differ (NaN equals NaN;
    a missing leaf or one of another shape or dtype counts whole)."""
    out = {}
    for path, mask_path in rules:
        w = np.asarray(leaf(want, path))
        g = leaf(got, path)
        if g is None or np.shape(g) != w.shape or np.asarray(g).dtype != w.dtype:
            out[path] = int(w.size)
            continue
        g = np.asarray(g)
        same = g == w
        if w.dtype.kind == "f":
            same |= np.isnan(g) & np.isnan(w)
        m = _mask(want, mask_path)
        if m is not None:
            m = np.asarray(m, bool).reshape(m.shape + (1,) * (w.ndim - m.ndim))
            same |= ~np.broadcast_to(m, w.shape)
        bad = int(w.size - np.count_nonzero(same))
        if bad:
            out[path] = bad
    return out
