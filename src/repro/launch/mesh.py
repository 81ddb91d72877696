"""Production mesh builders.

Defined as FUNCTIONS so importing this module never touches jax device
state. The dry-run entrypoint (dryrun.py) sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 BEFORE importing jax;
everything else sees the real (single-CPU) device set.

Topology model (TPU v5e-class): one pod = 16 x 16 = 256 chips on ICI
(~50 GB/s/link); the multi-pod mesh adds a leading "pod" axis whose
collectives cross the slower DCI — the hierarchical gradient reduction in
train_step keeps that hop to 1/16 of the gradient bytes.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def _make_mesh(shape, axes) -> Mesh:
    """`jax.make_mesh` with every axis Auto (GSPMD-propagated)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_host_mesh(shape, axes) -> Mesh:
    """Arbitrary mesh over host devices (tests / reduced dry-runs)."""
    return _make_mesh(tuple(shape), tuple(axes))


def make_row_member_mesh(devices, member_shards: int, *,
                         row_axis: str = "shard",
                         member_axis: str = "member") -> Mesh:
    """The 2D (row, member) mesh for K-sharded stacked ensembles.

    ``devices`` (an explicit device list, so runtimes pin their own
    subset) reshapes to (Dr, Dk) = (len(devices) // member_shards,
    member_shards): collectives over ``row_axis`` stay within one
    row-subgroup of Dr devices (halo/stride/gather transports never cross
    the member axis), while the K members split Dk ways along
    ``member_axis``.

    Mirrors ``_halo.exchange_stride_start``'s loud non-pow2 rejection:
    a Dk that does not divide the device count would otherwise surface as
    an opaque XLA reshape/shard_map error deep inside the launch, so the
    contract is enforced here with the fallback named.
    """
    import numpy as np

    devices = list(devices)
    count = len(devices)
    dk = int(member_shards)
    if dk < 1 or count % dk:
        raise ValueError(
            f"2D (row, member) mesh needs member_shards to divide the "
            f"device count: {count} devices cannot split into "
            f"(rows, members) = ({count / dk if dk else '?'}, {dk}). "
            f"Pass member_shards=1 (or a divisor of {count}) to fall "
            f"back to the replicated 1D row mesh.")
    return Mesh(np.asarray(devices).reshape(count // dk, dk),
                (row_axis, member_axis))


# Hardware constants for the roofline (TPU v5e-class, per chip)
PEAK_FLOPS_BF16 = 197e12  # FLOP/s
HBM_BW = 819e9  # B/s
ICI_BW = 50e9  # B/s per link
CHIP_HBM_BYTES = 16 * 1024**3
