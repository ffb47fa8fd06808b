"""The attached chip: the platform probe and the one table of peaks.

``on_tpu()`` is the only platform probe the kernel modules use. It has
no fallback: if JAX cannot initialise its backend the error surfaces at
the first kernel launch instead of quietly selecting interpret mode or
an XLA reference. Tests and the static-analysis dry-traces steer it by
patching ``paddle_tpu.device.chip.on_tpu`` (the kernel modules call it
through this module, so one patch covers all of them).

``CHIPS`` is keyed by ``jax.Device.device_kind`` (lower-cased). A
device that is not in the table is an error, never a default: a
utilization computed against another chip's peak is a wrong number
under a right name.

Source of every row: Google Cloud TPU documentation, the system
architecture page of that version ("TPU v5e": 197 TFLOP/s bf16 and
819 GB/s of HBM per chip; likewise v2, v3, v4, v5p, v6e). VMEM and HBM
capacities per generation are in ``device/vmem.py``.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

__all__ = ["on_tpu", "Chip", "CHIPS", "chip_spec"]


@functools.lru_cache(maxsize=1)
def on_tpu() -> bool:
    import jax

    return jax.default_backend() == "tpu"


class Chip(NamedTuple):
    generation: str        # key into device.vmem's VMEM/HBM budget tables
    peak_bf16_flops: float  # FLOP/s
    hbm_bytes_per_s: float


_V5E = Chip("v5e", 197e12, 819e9)
_V6E = Chip("v6e", 918e12, 1640e9)

#: jax device_kind (lower-cased) -> chip
CHIPS = {
    "tpu v5 lite": _V5E,
    "tpu v5e": _V5E,
    "tpu v5p": Chip("v5p", 459e12, 2765e9),
    "tpu v5": Chip("v5p", 459e12, 2765e9),
    "tpu v6 lite": _V6E,
    "tpu v6e": _V6E,
    "tpu v4": Chip("v4", 275e12, 1228e9),
    "tpu v3": Chip("v3", 123e12, 900e9),
    "tpu v2": Chip("v2", 45e12, 700e9),
}


def chip_spec(device) -> Chip:
    """The table row for a ``jax.Device`` (or a device_kind string)."""
    kind = getattr(device, "device_kind", device)
    spec = CHIPS.get(str(kind).lower())
    if spec is None:
        raise ValueError(
            f"device_kind {kind!r} is not in paddle_tpu.device.chip.CHIPS "
            f"(known: {sorted(CHIPS)}) — add its published peaks there; "
            "no other chip's numbers are assumed for it")
    return spec
