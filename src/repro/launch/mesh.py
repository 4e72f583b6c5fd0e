"""Production mesh definitions — thin forwarder.

Mesh construction is owned by :mod:`repro.dist.sharding`; this module
keeps the historical ``repro.launch.mesh`` import path alive. Both are
FUNCTIONS, not module-level constants — importing never touches jax
device state (required so smoke tests see 1 device while the dry-run
sees 512)."""
from __future__ import annotations

from repro.dist.sharding import (  # noqa: F401
    make_local_mesh,
    make_production_mesh,
)
