"""Thin re-export shim: the mesh constructors live in ``repro.dist.meshes``
(the logical-axis sharding subsystem) since the dist layer owns everything
mesh-shaped. Import from there in new code."""
from repro.dist.meshes import (  # noqa: F401
    make_host_mesh,
    make_production_mesh,
)

__all__ = ["make_host_mesh", "make_production_mesh"]
