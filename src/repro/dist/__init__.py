"""``repro.dist`` — single owner of distribution concerns (DESIGN §4/§6).

Modules:

* :mod:`repro.dist.sharding` — the PartitionSpec engine (param / batch /
  opt-state / cache specs from pytree paths), mesh construction, and the
  ambient-mesh ``constrain`` helper model code uses.
* :mod:`repro.dist.compression` — int8 cross-pod gradient reduction
  (``int8_psum`` / ``psum_tree``) and the analytic ``wire_bytes`` model.

Everything above this package (models, train, launch, serve, scripts)
talks to meshes, specs, and collectives only through these modules.
"""
from repro.dist import compression, sharding  # noqa: F401
