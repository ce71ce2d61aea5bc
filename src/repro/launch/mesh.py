"""Production mesh construction.

Functions, not module-level constants, so importing never touches jax device
state.  Single pod: 16×16 = 256 chips (``data`` × ``model``); multi-pod:
2×16×16 = 512 chips with a leading ``pod`` axis (data-parallel across pods —
the slowest links carry only gradient AllReduce, exactly the paper's
cross-machine traffic profile).
"""
from __future__ import annotations

import math

import jax
from jax.sharding import AxisType, Mesh


def _make_mesh(shape, axes, devices) -> Mesh:
    return jax.make_mesh(
        shape, axes,
        axis_types=(AxisType.Auto,) * len(axes),
        devices=devices)


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    ndev = math.prod(shape)
    devices = jax.devices()
    if len(devices) < ndev:
        raise RuntimeError(
            f"mesh {shape} needs {ndev} devices, have {len(devices)} — "
            "the dry-run entry point must set "
            "XLA_FLAGS=--xla_force_host_platform_device_count=512 before "
            "any jax import")
    return _make_mesh(shape, axes, devices[:ndev])


def make_host_mesh(data: int = 1, model: int = 1) -> Mesh:
    """Small mesh over whatever local devices exist (tests / examples)."""
    devices = jax.devices()[: data * model]
    return _make_mesh((data, model), ("data", "model"), devices)


def fit_spmd_mesh(num_trainers: int, num_table_shards: int,
                  ndev: "int | None" = None) -> "tuple[int, int] | None":
    """``(data, model)`` shape for the trainer's spmd step, or ``None``
    when the local devices cannot host it.

    The ``model`` axis must be EXACTLY ``num_table_shards`` — the
    row-sharded entity table places one ``(rows, d)`` block per model-axis
    device (``kge_param_specs`` enforces ``S == mesh.shape['model']``); a
    dense table (``num_table_shards == 1``) means a 1-wide model axis.
    The ``data`` axis is the largest divisor of ``num_trainers`` that fits
    the remaining devices (partitions must split evenly over it —
    ``BatchShardings.check``).  The same rule drives ``--spmd`` auto-on in
    the CLI and ``TrainConfig.spmd=None`` auto-detection.
    """
    ndev = jax.device_count() if ndev is None else ndev
    model = max(num_table_shards, 1)
    if model > ndev:
        return None
    data = max(d for d in range(1, ndev // model + 1)
               if num_trainers % d == 0)
    return data, model


# Published peaks of one chip, keyed by ``jax.Device.device_kind``
# (Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s HBM,
# 1,600 Gbit/s of interconnect over 4 links).  A kind that is not here is
# an error, never a default.
CHIP_PEAKS = {
    "TPU v5 lite": {
        "peak_flops_bf16": 197e12,   # FLOP/s per chip
        "hbm_bw": 819e9,             # bytes/s per chip
        "ici_bw": 50e9,              # bytes/s per link
    },
}


def chip_peaks(device_kind: str) -> dict:
    """The peak table of ``device_kind``; raises for a kind it lacks."""
    try:
        return CHIP_PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no peak table for device kind {device_kind!r} "
            f"(known: {sorted(CHIP_PEAKS)})") from None
