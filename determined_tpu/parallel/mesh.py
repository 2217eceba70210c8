"""Device-mesh construction for TPU slices.

An allocation in determined-tpu is "a set of chips with a fixed ICI mesh"
(SURVEY.md §7).  This module turns a flat device list into a named
`jax.sharding.Mesh` with the canonical axis names used across the framework:

  data     — pure data parallelism (replicated params); rides DCN across slices
  pipeline — pipeline (layer-stage) parallelism; stage boundaries exchange
             activations once per microbatch, so it sits next to `data` on
             the slower axes
  fsdp     — fully-sharded data parallelism (ZeRO-3 analogue); intra-slice ICI
  tensor   — Megatron-style tensor parallelism; innermost, fastest ICI axis
  context  — sequence/context parallelism (ring attention)
  expert   — MoE expert parallelism

Axes of size 1 are always present so PartitionSpecs can reference any axis
unconditionally — XLA treats size-1 mesh axes as free.
"""

from __future__ import annotations

import dataclasses
import logging
import math
from typing import Any, Iterable, Mapping, Optional, Sequence

import numpy as np

logger = logging.getLogger("determined_tpu.parallel")

AXIS_ORDER = ("data", "pipeline", "fsdp", "expert", "context", "tensor")


@dataclasses.dataclass(frozen=True)
class MeshConfig:
    """Declarative mesh request, part of an experiment's resources config.

    Sizes of -1 mean "absorb all remaining devices" (at most one axis may be
    -1, like a numpy reshape).  Unspecified axes default to 1.
    """

    data: int = -1
    pipeline: int = 1
    fsdp: int = 1
    expert: int = 1
    context: int = 1
    tensor: int = 1

    def sizes(self) -> tuple:
        return tuple(getattr(self, a) for a in AXIS_ORDER)

    def resolve(self, n_devices: int) -> "MeshConfig":
        """Fill in any -1 axis from the device count and validate the product."""
        sizes = list(self.sizes())
        unknown = [i for i, s in enumerate(sizes) if s == -1]
        if len(unknown) > 1:
            raise ValueError(f"at most one mesh axis may be -1, got {self}")
        known = math.prod(s for s in sizes if s != -1)
        if unknown:
            if n_devices % known != 0:
                raise ValueError(
                    f"{n_devices} devices not divisible by fixed axes product {known}"
                )
            sizes[unknown[0]] = n_devices // known
        if math.prod(sizes) != n_devices:
            raise ValueError(
                f"mesh {dict(zip(AXIS_ORDER, sizes))} needs {math.prod(sizes)} "
                f"devices, allocation has {n_devices}"
            )
        return MeshConfig(**dict(zip(AXIS_ORDER, sizes)))

    def resolvable(self, n_devices: int) -> bool:
        """True when `resolve(n_devices)` would succeed — the elastic
        feasibility check (preflight DTL204, Trainer resize) without the
        exception control flow."""
        try:
            self.resolve(n_devices)
            return True
        except ValueError:
            return False

    @staticmethod
    def from_dict(d: Mapping[str, int]) -> "MeshConfig":
        unknown = set(d) - set(AXIS_ORDER)
        if unknown:
            raise ValueError(f"unknown mesh axes {sorted(unknown)}; valid: {AXIS_ORDER}")
        return MeshConfig(**dict(d))


def mesh_shape_for_devices(n_devices: int, config: Optional[MeshConfig] = None) -> tuple:
    cfg = (config or MeshConfig()).resolve(n_devices)
    return cfg.sizes()


def create_mesh(
    config: Optional[MeshConfig] = None,
    devices: Optional[Sequence[Any]] = None,
):
    """Build a named Mesh over `devices` (default: all visible devices).

    Uses `mesh_utils.create_device_mesh` so that on real TPU slices the
    logical axes are laid out along physical ICI rings (innermost axis =
    tightest ring, which is why `tensor` is last in AXIS_ORDER).
    """
    import jax
    from jax.experimental import mesh_utils
    from jax.sharding import Mesh

    if devices is None:
        devices = jax.devices()
    devices = list(devices)
    shape = mesh_shape_for_devices(len(devices), config)
    try:
        dev_array = mesh_utils.create_device_mesh(shape, devices=devices)
    except (ValueError, AssertionError, NotImplementedError) as e:
        # A shape the physical topology cannot host (or a device subset,
        # e.g. an elastic resize over a prefix of the chips): device order
        # is still a correct mesh, only the ICI ring placement is lost —
        # say so instead of degrading quietly.
        logger.warning(
            "create_device_mesh could not lay %s over %d %s devices (%s); "
            "using plain device order", dict(zip(AXIS_ORDER, shape)),
            len(devices), devices[0].platform, e)
        dev_array = np.asarray(devices).reshape(shape)
    return Mesh(dev_array, AXIS_ORDER)


def single_device_mesh(device: Optional[Any] = None):
    """A 1-chip mesh (all axes size 1) — used by single-slot trials."""
    import jax

    if device is None:
        device = jax.devices()[0]
    return create_mesh(MeshConfig(data=1), [device])


def ambient_mesh():
    """The abstract mesh in effect for the current trace, or None.

    `jax.sharding.set_mesh` (the Trainer, the deviceless compile recipe)
    and `use_abstract_mesh` both install it; inside a `shard_map` body its
    `manual_axes` name the axes already bound."""
    import jax

    mesh = jax.sharding.get_abstract_mesh()
    return None if mesh.empty else mesh


def on_tpu(devices: Optional[Iterable[Any]] = None) -> bool:
    """THE answer to "is this a TPU" (kernel selection, bf16 compute,
    peak-FLOPs lookup): the platform of the devices being compiled for.

    `devices` when the caller holds them (a Trainer's mesh, an engine's
    device). Else the ambient mesh's abstract device — which is what makes
    a TPU compile from a CPU default backend (the deviceless topology
    recipe, a compile-farm worker) select the Mosaic kernels instead of
    whatever the host happens to run; an abstract device carries only its
    kind, and every TPU generation's kind starts with "TPU". Else the
    process's default backend.
    """
    import jax

    if devices is not None:
        return next(iter(devices)).platform == "tpu"
    mesh = ambient_mesh()
    if mesh is not None and mesh.abstract_device is not None:
        return mesh.abstract_device.device_kind.startswith("TPU")
    return jax.default_backend() == "tpu"
