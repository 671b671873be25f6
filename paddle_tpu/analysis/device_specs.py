"""THE hardware spec table (ISSUE 13): peak FLOPs per dtype, HBM
bandwidth, interconnect bandwidth, and per-kernel launch overhead for
every accelerator the repo reasons about statically.

Before this module the numbers lived as scattered literals —
`bench_roofline.py`'s ``HBM_GBS = 819e9``, `bench_mfu.py`'s
``197e12`` peak-FLOPs denominator, `bench.py`'s ``918e12 if "v6"``
device-kind switch, `bench_serving.py`'s weight-read-bound divisor —
and any disagreement between them silently skewed an MFU or a bound
fraction. They now live HERE once; the benches and the static roofline
pass (`analysis/roofline.py`) read the same row.

The numbers are the public per-chip figures (dense matmul peak; HBM
bytes/s; aggregate ICI bytes/s), deliberately round — the pass that
consumes them predicts bound CLASSES and ~10-15% step-time envelopes,
not microseconds. ``launch_overhead_s`` is the fixed per-kernel issue
cost (sub-microsecond dispatch on TPU; the OPBENCH
``kernels_per_step`` counter measures how many a step pays). The
explicit ``cpu-container`` row exists so audits CAN price
the CI container itself; it is never auto-selected — on a non-TPU host
`get_spec()` defaults to the repo's baseline serving chip (v5e),
because pre-silicon prediction for the TARGET device is the point of
the static pass.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional

__all__ = [
    "DEFAULT_HBM_HEADROOM", "DEVICE_SPECS", "DeviceSpec",
    "DEFAULT_DEVICE", "auto_hbm_budget", "get_spec",
    "spec_for_device_kind",
]

# the repo's baseline serving/training chip — every bench bound was
# derived against it
DEFAULT_DEVICE = "tpu-v5e"


@dataclasses.dataclass(frozen=True)
class DeviceSpec:
    """One accelerator row. `peak_flops` maps dtype name -> dense-matmul
    FLOP/s; dtypes without a row resolve via `peak_for` (f16 rides the
    bf16 entry, f64/unknown the f32 one). Bandwidths are bytes/s."""

    name: str
    peak_flops: Dict[str, float]
    hbm_gbs: float            # HBM bytes/s
    ici_gbs: float            # aggregate per-chip interconnect bytes/s
    launch_overhead_s: float  # fixed issue cost per kernel launch
    hbm_bytes: int            # capacity (informational; TPU702 budgets)

    def peak_for(self, dtype) -> float:
        d = str(dtype)
        if d in self.peak_flops:
            return self.peak_flops[d]
        if d in ("float16", "bfloat16"):
            return self.peak_flops.get("bfloat16",
                                       max(self.peak_flops.values()))
        if d in ("int8", "uint8", "int4", "uint4", "float8_e4m3fn",
                 "float8_e5m2"):
            return self.peak_flops.get("int8",
                                       self.peak_flops.get("bfloat16",
                                       max(self.peak_flops.values())))
        # f32/f64/int32/unknown: the conservative rate
        return self.peak_flops.get("float32",
                                   min(self.peak_flops.values()))

    def ridge_point(self, dtype) -> float:
        """Arithmetic intensity (FLOPs/byte) where compute time equals
        HBM time — below it an op is bandwidth-bound on this chip."""
        return self.peak_for(dtype) / self.hbm_gbs

    def to_dict(self) -> dict:
        return {
            "name": self.name,
            "peak_flops": dict(self.peak_flops),
            "hbm_gbs": self.hbm_gbs,
            "ici_gbs": self.ici_gbs,
            "launch_overhead_s": self.launch_overhead_s,
            "hbm_bytes": self.hbm_bytes,
        }


# bf16 = published dense peak; int8 = 2x where the generation doubles
# int8 throughput; f32 = bf16/8 (the MXU mixed-precision rate TPU301
# warns about). 197e12 / 819e9 are the EXACT literals the benches used.
DEVICE_SPECS: Dict[str, DeviceSpec] = {
    "tpu-v4": DeviceSpec(
        name="tpu-v4",
        peak_flops={"bfloat16": 275e12, "int8": 275e12,
                    "float32": 275e12 / 8},
        hbm_gbs=1228e9, ici_gbs=300e9, launch_overhead_s=5e-7,
        hbm_bytes=32 << 30),
    "tpu-v5e": DeviceSpec(
        name="tpu-v5e",
        peak_flops={"bfloat16": 197e12, "int8": 394e12,
                    "float32": 197e12 / 8},
        hbm_gbs=819e9, ici_gbs=200e9, launch_overhead_s=5e-7,
        hbm_bytes=16 << 30),
    "tpu-v5p": DeviceSpec(
        name="tpu-v5p",
        peak_flops={"bfloat16": 459e12, "int8": 918e12,
                    "float32": 459e12 / 8},
        hbm_gbs=2765e9, ici_gbs=600e9, launch_overhead_s=5e-7,
        hbm_bytes=95 << 30),
    "tpu-v6e": DeviceSpec(
        name="tpu-v6e",
        peak_flops={"bfloat16": 918e12, "int8": 1836e12,
                    "float32": 918e12 / 8},
        hbm_gbs=1640e9, ici_gbs=448e9, launch_overhead_s=5e-7,
        hbm_bytes=32 << 30),
    # the CI/dev container: no MXU, DDR-class bandwidth, python-side
    # dispatch. Selected only EXPLICITLY (see module docstring).
    "cpu-container": DeviceSpec(
        name="cpu-container",
        peak_flops={"bfloat16": 0.2e12, "int8": 0.2e12,
                    "float32": 0.4e12},
        hbm_gbs=20e9, ici_gbs=10e9, launch_overhead_s=5e-6,
        hbm_bytes=16 << 30),
}


def spec_for_device_kind(kind: str) -> DeviceSpec:
    """Row for the ``device_kind`` string of an attached device ("TPU
    v5 lite", "TPU v4", ...). A kind the table does not know raises: a
    peak taken from the wrong row skews every utilization computed from
    it, so a device that is not in the table is an error, not a
    default."""
    k = (kind or "").lower()
    if "v6" in k:
        return DEVICE_SPECS["tpu-v6e"]
    if "v5p" in k:
        return DEVICE_SPECS["tpu-v5p"]
    if "v5 lite" in k or "v5e" in k or "v5lite" in k:
        return DEVICE_SPECS["tpu-v5e"]
    if "v4" in k:
        return DEVICE_SPECS["tpu-v4"]
    raise KeyError(
        f"no device spec row for device_kind {kind!r}; rows: "
        f"{sorted(DEVICE_SPECS)} — add the chip to "
        f"analysis/device_specs.py with its published peaks")


# fraction of a device row's HBM held back from the auto-derived
# budget: XLA workspace, runtime reserves, and the fragmentation slack
# a liveness estimate cannot see. 10% of 16 GiB leaves the v5e row a
# ~14.4 GiB budget — the same order as the usable-HBM figures serving
# stacks report on that chip.
DEFAULT_HBM_HEADROOM = 0.10


def auto_hbm_budget(device: Optional[object] = None, *,
                    headroom: float = DEFAULT_HBM_HEADROOM) -> int:
    """Default per-chip HBM byte budget for a device row: capacity
    minus a `headroom` fraction. The ONE derivation shared by TPU702's
    auto-armed budget and the autotuner's feasibility gate
    (analysis/tuner.py) — both compare per-chip byte estimates from
    the liveness pass against it, so they must agree on what "fits"
    means."""
    spec = get_spec(device)
    if not 0.0 <= headroom < 1.0:
        raise ValueError(
            f"headroom must be a fraction in [0, 1), got {headroom!r}")
    return int(spec.hbm_bytes * (1.0 - headroom))


def get_spec(device: Optional[object] = None) -> DeviceSpec:
    """Resolve a `DeviceSpec`: a `DeviceSpec` passes through, a string
    looks up the table (KeyError lists the rows), and None detects:
    with a TPU attached, the row of its device_kind (an unknown kind
    raises, see `spec_for_device_kind`). Only with NO accelerator
    attached — the static-audit case, tracing on a CPU host to predict
    for the serving chip — does None mean the documented
    `DEFAULT_DEVICE` row."""
    if isinstance(device, DeviceSpec):
        return device
    if device is not None:
        try:
            return DEVICE_SPECS[str(device)]
        except KeyError:
            raise KeyError(
                f"unknown device spec {device!r}; rows: "
                f"{sorted(DEVICE_SPECS)}") from None
    import jax

    if jax.default_backend() == "tpu":
        return spec_for_device_kind(jax.devices()[0].device_kind)
    return DEVICE_SPECS[DEFAULT_DEVICE]
