"""jit wrapper for the SSD scan kernel (compiled on TPU, interpreted
elsewhere)."""
from __future__ import annotations

import jax

from repro.kernels.ssd_scan.ref import ssd_reference
from repro.kernels.ssd_scan.ssd_scan import ssd_scan_pallas


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def ssd_scan(x, dt, A, B, C, chunk: int):
    """Chunked SSD scan; same contract as models.ssm.ssd_reference minus the
    final state (training path does not need it).  Compiled on the TPU,
    interpreted elsewhere."""
    return ssd_scan_pallas(x, dt, A, B, C, chunk, interpret=not _on_tpu())


def ssd_scan_ref(x, dt, A, B, C, chunk: int):
    y, _ = ssd_reference(x, dt, A, B, C, chunk)
    return y
