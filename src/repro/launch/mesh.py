"""Production mesh definitions.

Defined as FUNCTIONS (never module-level constants) so importing this module never
touches jax device state — the dry-run must set XLA_FLAGS before first jax init.
"""
from __future__ import annotations

from typing import Tuple

import jax


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]):
    """``jax.make_mesh`` with every axis Auto (shard_map handles Manual
    itself)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


# production mesh geometry (also consumed by benchmarks/roofline.py, which
# must not instantiate the mesh — that would lock the jax device count)
PROD_DATA = 16
PROD_MODEL = 16
PROD_PODS = 2
# one four-chip host (a v5e 2x2): one EF client per chip, no model axis
HOST4 = (4, 1)


def _shrink_shape(shape: Tuple[int, ...], n_devices: int) -> Tuple[int, ...]:
    """Fit a production mesh shape onto fewer devices, left-to-right
    (pod-major): each axis takes the largest divisor of the remaining device
    count no bigger than its production size. The pod axis is first, so a
    forced-host-device run keeps the full pod count whenever it can —
    (2, 16, 16) on 8 devices becomes (2, 4, 1), preserving the two-pod
    topology the hierarchical tests exercise."""
    rem = n_devices
    out = []
    for want in shape:
        for d in range(min(want, rem), 0, -1):
            if rem % d == 0:
                out.append(d)
                rem //= d
                break
    return tuple(out)


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (data=16, model=16) = 256 chips (TPU v5e pod).
    Multi-pod:  (pod=2, data=16, model=16) = 512 chips across DCI.
    On hosts with fewer devices (CI's forced-8-device CPU runs) the shape
    shrinks pod-major (``_shrink_shape``) instead of failing, so
    ``--mesh multi_pod`` is portable to any device count."""
    shape = (PROD_PODS, PROD_DATA, PROD_MODEL) if multi_pod \
        else (PROD_DATA, PROD_MODEL)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n_dev = len(jax.devices())
    need = 1
    for s in shape:
        need *= s
    if n_dev < need:
        shape = _shrink_shape(shape, n_dev)
    return make_mesh(shape, axes)


def make_host4_mesh():
    """(data=4, model=1) on the first four devices: one four-chip host, one
    EF client per chip. Fewer devices are refused, not shrunk: the spec's
    client count (and its batch check) is the mesh's."""
    devs = jax.devices()
    need = HOST4[0] * HOST4[1]
    if len(devs) < need:
        raise ValueError(f"mesh 'host4' needs {need} devices, JAX found "
                         f"{len(devs)}")
    return jax.make_mesh(HOST4, ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2,
                         devices=devs[:need])


def client_axes(mesh) -> Tuple[str, ...]:
    """The client axes in POD-MAJOR order — ('pod', 'data') whenever the pod
    axis exists, regardless of the mesh's own axis order. This is the order
    ``shardings.ef_state_pspecs`` shards client state with and the order the
    hierarchical runtimes compose client_index with (client i belongs to pod
    i // (n/pods), core/hierarchy.pod_mean) — both runtimes must agree on
    who is in which pod."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def data_axes(mesh) -> Tuple[str, ...]:
    return client_axes(mesh)


def dp_size(mesh) -> int:
    s = 1
    for a in data_axes(mesh):
        s *= mesh.shape[a]
    return s


def make_smoke_mesh():
    """1-device mesh for CPU tests."""
    return make_mesh((1, 1), ("data", "model"))
