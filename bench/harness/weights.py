"""Weights made from the seed, on the device, in one jitted call.

A configuration names its plain reference (``"reference"``), and that
module's ``layout(cfg)`` names every leaf with its shape, dtype, kind
and scale. ``make`` draws them all from the seed in the served dtype,
each leaf straight into its sharding where one is given: the values do
not depend on it (``jax_threefry_partitionable``). The program and the
plain reference both take their weights from here, so neither takes
anything the other made.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

from harness import manifest


def layout(cfg: dict) -> dict:
    """name -> (shape, dtype, kind, scale), from the configuration's
    reference. ``kind`` is ``normal`` (zero mean) or ``gain`` (mean 1)."""
    return manifest.reference(cfg["reference"]).layout(cfg)


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative seed, 64 bits of it."""
    seed = int(seed)
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def spread(lay: dict, mesh, axis: str) -> dict:
    """The benchmark's own placement of every leaf over ``mesh``: its
    last dimension split over ``axis`` where the axis's size divides
    it, else whole on every device."""
    n = mesh.shape[axis]

    def spec(shape):
        if not shape or shape[-1] % n:
            return PartitionSpec()
        return PartitionSpec(*([None] * (len(shape) - 1)), axis)

    return {name: NamedSharding(mesh, spec(shape))
            for name, (shape, *_) in lay.items()}


def make(cfg: dict, seed: int,
         shardings: dict | None = None) -> dict[str, jax.Array]:
    """Every leaf of ``layout(cfg)`` for ``seed``; leaf ``i`` of the
    sorted layout is drawn from ``fold_in(key, i)``. ``shardings`` (name
    -> sharding) places each leaf as it is drawn, so that no leaf is
    ever whole on one device."""
    lay = layout(cfg)

    def draw(key):
        out = {}
        for i, name in enumerate(sorted(lay)):
            shape, dt, kind, scale = lay[name]
            x = jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32) * scale
            out[name] = (x + 1.0 if kind == "gain" else x).astype(dt)
        return out

    if shardings is None:
        return jax.jit(draw)(seed_key(seed))
    return jax.jit(draw, out_shardings=shardings)(seed_key(seed))
