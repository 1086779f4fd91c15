"""jit'd public wrapper for the SSD kernel."""
from __future__ import annotations

from .kernel import ssd_scan
from .ref import ssd_ref


def ssd(x, dt, a, B_, C_, *, chunk: int = 128, mode: str = "pallas",
        interpret: bool):
    if mode == "pallas":
        return ssd_scan(x, dt, a, B_, C_, chunk=chunk, interpret=interpret)
    return ssd_ref(x, dt, a, B_, C_)
