"""Production mesh construction (importing this module never touches jax
device state — the mesh is built lazily inside the function).

Every mesh here uses ``Auto`` axis types: the sharding ``Rules`` place
params and batches with ``NamedSharding`` and leave propagation to the
compiler, which is what ``Auto`` axes do. ``jax.make_mesh`` otherwise
defaults to ``Explicit`` axes, under which ops such as the embedding
gather must state their output sharding and fail without it.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """v5e-256 pod mesh: (data=16, model=16); multi-pod adds a leading pure-DP
    'pod' axis over DCN: (pod=2, data=16, model=16) = 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_host_mesh(model: int = 1):
    """Small mesh over whatever devices exist (tests / local runs)."""
    n = len(jax.devices())
    model = min(model, n)
    return _mesh((n // model, model), ("data", "model"))


def make_serving_mesh(spec: str):
    """Parse a serving CLI ``--mesh dp,tp`` spec into a (data, model) mesh.

    ``"2,1"`` = 2-way data parallel, ``"1,2"`` = 2-way tensor parallel,
    ``"4,2"`` = both. The product must not exceed the visible device count;
    on a CPU container grow it with the host-device trick
    (``XLA_FLAGS=--xla_force_host_platform_device_count=N`` before the
    process starts). ``"1,1"`` returns ``None`` — the unmeshed single-
    device runtime, byte-identical to omitting ``--mesh``.
    """
    try:
        dp, tp = (int(p) for p in spec.split(","))
    except ValueError:
        raise ValueError(f"--mesh wants 'dp,tp' (two integers), got "
                         f"{spec!r}") from None
    if dp < 1 or tp < 1:
        raise ValueError(f"--mesh axes must be >= 1, got {spec!r}")
    if dp == tp == 1:
        return None
    n = len(jax.devices())
    if dp * tp > n:
        raise ValueError(
            f"--mesh {spec} needs {dp * tp} devices but only {n} visible; "
            f"on CPU set XLA_FLAGS=--xla_force_host_platform_device_count="
            f"{dp * tp}")
    return _mesh((dp, tp), ("data", "model"))
