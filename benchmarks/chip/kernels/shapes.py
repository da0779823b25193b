"""Shapes one program call (one rig frame) hands the kernels, from a
configuration."""

from __future__ import annotations


def level_shapes(config: dict) -> list[tuple[int, int]]:
    orb = config["orb"]
    h, w, out = orb["height"], orb["width"], []
    for _ in range(orb["n_levels"]):
        out.append((h, w))
        h = int(round(h / orb["scale_factor"]))
        w = int(round(w / orb["scale_factor"]))
    return out


def cameras(config: dict) -> int:
    return config["rig"]["n_cameras"]


def pairs(config: dict) -> int:
    return len(config["rig"]["pairs"])
