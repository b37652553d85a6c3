"""Meshes over the test process's virtual CPU devices."""

import math

import jax

from edl_tpu.parallel import MeshSpec, build_mesh


def mesh_of(**axes):
    """A mesh of the named axes' sizes (``mesh_of(dp=2, tp=2)``; no axis:
    a mesh of one device) over the first devices."""
    n = math.prod(axes.values())
    return build_mesh(MeshSpec(**{"dp": 1, **axes}), jax.devices()[:n])
