"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.

One table for every roofline, utilization and modeled-time figure in the
tree.  The key is the device kind JAX reports, never the backend name:
"tpu" covers chips whose peaks differ several-fold, and a number taken
from the wrong row is worse than no number.  An accelerator kind without
a row raises; the CPU (the test path) has no row and claims no device
peak — its callers measure a memcpy instead (``memory._memcpy_probe_gbs``).
"""

from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class ChipPeaks:
    bf16_flops: float      # FLOP/s
    int8_ops: float        # OP/s
    hbm_gbs: float         # GB/s
    ici_gbs: float         # GB/s per chip, chip-to-chip interconnect
    dcn_gbs: float         # GB/s per host NIC, between slices
    host_link_gbs: float   # GB/s per direction, host <-> device


PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 393 TOP/s
    # int8, 16 GB HBM2e at 819 GB/s, 1,600 Gbit/s of interconnect per
    # chip (= 200 GB/s).  DCN (~200 Gbit/s per host NIC) and the host
    # link (PCIe gen3 x16, ~16 GB/s per direction in practice) are not
    # in that table; they are this repo's earlier estimates, unmeasured.
    "TPU v5 lite": ChipPeaks(bf16_flops=197e12, int8_ops=393e12,
                             hbm_gbs=819.0, ici_gbs=200.0, dcn_gbs=25.0,
                             host_link_gbs=16.0),
}


def chip_peaks(device_kind):
    """The row for ``device_kind``, or None for the CPU.  Any other kind
    without a row is an error, not a default."""
    if device_kind == "cpu":
        return None
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r}; add a "
            f"row with its source to {__name__}.PEAKS") from None


def current_device_kind():
    """``device_kind`` of the first device of the default backend."""
    import jax

    return jax.devices()[0].device_kind
