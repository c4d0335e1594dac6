"""Public wrappers for the flash attention kernels.

``attention(...)`` picks the Pallas kernel on TPU and the blockwise-XLA path
elsewhere (Pallas does not lower to the CPU backend; interpret mode is for
validation, not speed).
"""
from __future__ import annotations

from repro.kernels.attention.flash import flash_decode, flash_prefill
from repro.kernels.attention.ref import decode_ref, mha_ref
from repro.kernels.efe.ops import on_tpu


def attention(q, k, v, *, causal: bool = True, window: int = 0,
              q_offset: int = 0, interpret: bool | None = None):
    """Prefill/train attention; kernel on TPU, oracle elsewhere."""
    if on_tpu() or interpret:
        return flash_prefill(q, k, v, causal=causal, window=window,
                             q_offset=q_offset,
                             interpret=bool(interpret) and not on_tpu())
    return mha_ref(q, k, v, causal=causal, window=window, q_offset=q_offset)


def decode_attention(q, k, v, *, position: int, window: int = 0,
                     interpret: bool | None = None):
    if on_tpu() or interpret:
        return flash_decode(q, k, v, position=position, window=window,
                            interpret=bool(interpret) and not on_tpu())
    return decode_ref(q, k, v, position=position, window=window)
