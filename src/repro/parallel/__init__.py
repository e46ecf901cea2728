"""Parallelism substrate: sharding hints, tensor-parallel context, meshes."""

from repro.parallel.compat import auto_mesh

__all__ = ["auto_mesh"]
