"""Pallas TPU kernels for the substrate's compute hot spots.

The WRATH paper itself has no kernel-level contribution (it is a
control-plane resilience system); these kernels cover the two dominant
compute hot spots of the model substrate per the hardware-adaptation
directive: blockwise flash attention (8/10 archs) and the Mamba-2 SSD
chunked scan (ssm/hybrid archs).  Validated in interpret mode against the
pure-jnp oracles in ``ref.py``.  The serving decode step runs one more,
``ssd_decode`` (the Mamba-2 state update of the occupied slots, in
place), called by ``models.ssm`` where the step is lowered for the TPU.

Block sizes are autotuned per input shape and persisted per device
signature (``repro.kernels.autotune``); callers that omit explicit
blocks get the cached winner transparently.
"""
from repro.kernels.autotune import (
    AutotuneCache,
    autotune_flash_attention,
    autotune_ssd_scan,
    device_signature,
)
from repro.kernels.ops import flash_attention, ssd_scan

__all__ = ["flash_attention", "ssd_scan", "AutotuneCache",
           "autotune_flash_attention", "autotune_ssd_scan",
           "device_signature"]
