"""repro.launch — mesh builders, dry-run, roofline, train/serve drivers.

NOTE: do not import .dryrun from here — it sets XLA_FLAGS at import time and
must only be imported as the program entry point (python -m
repro.launch.dryrun).
"""
from .mesh import make_host_mesh, make_mesh, make_production_mesh

__all__ = ["make_host_mesh", "make_mesh", "make_production_mesh"]
