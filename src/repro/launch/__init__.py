"""Launchers: production mesh, dry-run, train and serve drivers."""

from .mesh import make_debug_mesh, make_host_mesh, make_production_mesh

__all__ = ["make_debug_mesh", "make_host_mesh", "make_production_mesh"]
