"""Mesh construction. Functions only — importing this module never touches
jax device state (required so tests/benches see 1 device while dryrun.py sees
512 placeholder devices via XLA_FLAGS)."""
from __future__ import annotations

from typing import Optional, Tuple

import jax


def _auto(n: int):
    """Auto axis types: the sharding rules place arrays themselves, and
    ``jax.make_mesh`` now defaults to Explicit axes, which type-check every
    sharding (a scan carry that changes sharding then fails to trace)."""
    return (jax.sharding.AxisType.Auto,) * n


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod (TPU v5e); 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=_auto(len(axes)))


def make_host_mesh(model_parallel: int = 1):
    """Mesh over whatever devices actually exist (tests / local training)."""
    n = len(jax.devices())
    assert n % model_parallel == 0
    return jax.make_mesh((n // model_parallel, model_parallel),
                         ("data", "model"), axis_types=_auto(2))


def dp_axes(mesh) -> Tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def dp_size(mesh) -> int:
    out = 1
    for a in dp_axes(mesh):
        out *= mesh.shape[a]
    return out
