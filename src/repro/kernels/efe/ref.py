"""Oracle of the whole-window megakernel (:mod:`repro.kernels.efe.mega`)."""
from __future__ import annotations


def mega_window_ref(*args, **kwargs):
    """XLA oracle twin of the whole-window megakernel.

    Thin alias of :func:`repro.core.mega.mega_window` so the kernel package
    exposes the oracle next to the Pallas entry point.  Imported lazily to
    keep this module free of core-package imports at import time.
    """
    from repro.core import mega as mega_core
    return mega_core.mega_window(*args, **kwargs)
