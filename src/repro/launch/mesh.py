"""Mesh construction and the launchers' process set-up.

Functions, not module-level constants, so importing this module never
touches jax device state — the dry-run sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 before any jax
import, and everything else must keep seeing the real device count.

Axis semantics:
  * "pod"   — TPU pods connected by DCN (the paper's "global links");
  * "data"  — data parallelism within a pod (ICI);
  * "model" — tensor parallelism within a pod (ICI).

The flattened ("pod","data") gradient axis is pod-major, so rank id
distance approximates pod locality — the block-placement assumption under
which Bine trees cut global-link traffic (paper Sec. 2.2).

Every mesh is built with ``AxisType.Auto`` axes: the model code steers
layouts with GSPMD-style ``with_sharding_constraint`` hints, which
``jax.make_mesh``'s default ``Explicit`` axes turn into asserts.
"""

from __future__ import annotations

import os
from pathlib import Path
from typing import Optional, Sequence, Tuple

import jax
from jax.sharding import AxisType

#: in-checkout persistent compile cache, used when
#: ``JAX_COMPILATION_CACHE_DIR`` is not set (git-ignored)
COMPILE_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...],
              devices: Optional[Sequence] = None):
    """``jax.make_mesh`` with every axis ``Auto`` (GSPMD hints stay hints).

    ``devices`` defaults to all of ``jax.devices()``; pass a prefix to
    build a mesh over fewer devices than the process sees."""
    kw = {} if devices is None else {"devices": list(devices)}
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes), **kw)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def mesh_from_arg(arg: str, axes: Tuple[str, ...] = ("pod", "data", "model")):
    """The launchers' ``--mesh`` flag: ``"a,b,c"`` names the trailing
    ``len`` axes of ``axes``; empty puts every device on "data"."""
    if arg:
        shape = tuple(int(x) for x in arg.split(","))
        return make_mesh(shape, axes[-len(shape):])
    return make_mesh((len(jax.devices()), 1), ("data", "model"))


def enable_compile_cache() -> str:
    """Turn on jax's persistent compile cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is honoured as jax reads it;
    otherwise the cache sits at the fixed in-checkout
    :data:`COMPILE_CACHE_DIR` (the path is part of the cache key, so it
    never depends on a temp name, a pid or the time)."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(COMPILE_CACHE_DIR))
    return str(COMPILE_CACHE_DIR)


def dp_axes(mesh) -> Tuple[str, ...]:
    names = mesh.axis_names
    return tuple(a for a in names if a in ("pod", "data"))

