"""Production mesh construction.

Single pod: (data=16, model=16) = 256 chips (TPU v5e pod).
Multi-pod:  (pod=2, data=16, model=16) = 512 chips; the 'pod' axis carries
inter-pod data parallelism (optionally pipeline stages, see
parallel/pipeline.py) over the slower DCN/ICI links.

Functions, not module-level constants — importing this module never touches
jax device state (the dry-run sets XLA_FLAGS before first jax init).
"""

from __future__ import annotations

from repro.parallel.mesh import make_mesh as _make_mesh


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_test_mesh(shape=(2, 2), axes=("data", "model")):
    """Small mesh for CPU multi-device tests (subprocess with forced host
    device count)."""
    return _make_mesh(shape, axes)
