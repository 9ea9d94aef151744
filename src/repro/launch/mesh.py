"""Mesh construction: debug meshes for tests, production meshes for pods.

Meshes are built by FUNCTIONS (not module-level constants) so importing
this module never touches jax device state — `jax.devices()` locks the
device count on first call, and entry points like the dry-run need to set
``XLA_FLAGS`` first.

Two production shapes (see docs/architecture.md §4):

* single-pod: ``16x16 = 256`` chips, axes ``("data", "model")``;
* multi-pod:  ``2x16x16 = 512`` chips, axes ``("pod", "data", "model")`` —
  the pod axis extends data parallelism across pods and is what the
  multi-pod dry-run proves out.

Nothing in the step functions depends on the pod count: the sharding rule
tables use composite ``("pod", "data")`` entries that degrade gracefully
on the 2-axis mesh, so the same config scales to N pods by growing the
pod axis. ``make_debug_mesh(data, model)`` builds the small test/example
mesh over however many host devices exist (the 8-device integration tests
use a 2x4). ``make_local_mesh()`` puts every device this process sees on
the model axis — the serve CLI's default: 1x1 on one chip, 1x4 on a
four-chip host.
"""

from __future__ import annotations

import math

import jax
import numpy as np
from jax.sharding import Mesh


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """16x16 ("data", "model") mesh; 2x16x16 ("pod", "data", "model") with
    ``multi_pod``. Raises RuntimeError when fewer devices exist (the
    dry-run forces 512 host devices via XLA_FLAGS before importing jax).
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = math.prod(shape)
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices for the production mesh, have {len(devices)} "
            "(the dry-run sets XLA_FLAGS=--xla_force_host_platform_device_count=512)"
        )
    return Mesh(np.asarray(devices[:n]).reshape(shape), axes)


def make_debug_mesh(data: int = 1, model: int = 1) -> Mesh:
    """Small mesh over however many devices exist (tests / examples)."""
    n = data * model
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(f"need {n} devices, have {len(devices)}")
    return Mesh(np.asarray(devices[:n]).reshape(data, model),
                ("data", "model"))


def make_local_mesh() -> Mesh:
    """1 x N ("data", "model") mesh over every device this process sees."""
    return make_debug_mesh(1, len(jax.devices()))
