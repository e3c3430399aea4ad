"""The chip: which one the run is on, its published peaks, its memory.

Peaks are per chip and keyed by ``device_kind`` as JAX reports it.  A kind
that is not in the table is an error, never a default.
"""
from __future__ import annotations

from dataclasses import dataclass

# Google Cloud documentation, "TPU v5e" (Cloud TPU system architecture):
# 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2 at 819 GB/s per chip.
PEAKS = {
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bytes_s": 819e9,
                    "hbm_bytes": 16e9,
                    "source": "Google Cloud TPU v5e documentation"},
}


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


@dataclass(frozen=True)
class Peaks:
    kind: str
    flops: float          # FLOP/s, bf16
    hbm_bw: float         # bytes/s

    @classmethod
    def of(cls, kind: str) -> "Peaks":
        if kind not in PEAKS:
            raise KeyError(f"no peaks known for device kind {kind!r}; "
                           f"known: {sorted(PEAKS)}")
        p = PEAKS[kind]
        return cls(kind, p["flops_bf16"], p["hbm_bytes_s"])


def require_chips(n: int) -> list:
    """The first ``n`` TPU devices; raises ``NoChip`` otherwise.  Never falls
    back to the CPU."""
    import jax

    try:
        devs = jax.devices()
    except RuntimeError as e:       # no backend at all
        raise NoChip(f"JAX found no device: {e}") from None
    if devs[0].platform != "tpu":
        raise NoChip(f"needs a TPU, JAX found {devs[0].platform}")
    if len(devs) < n:
        raise NoChip(f"the cell needs {n} chips, JAX found {len(devs)}")
    return devs[:n]


def describe(devices: list) -> dict:
    """The result line's ``device`` entry, without the memory peak."""
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def memory_peak(devices: list) -> int | None:
    """Peak bytes in use on the fullest chip, where the backend reports it."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return int(max(peaks)) if peaks else None
