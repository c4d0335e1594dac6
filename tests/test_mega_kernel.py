"""The Pallas megakernel in interpret mode against its XLA oracle, with the
slot tape streamed from HBM, and compiled on a TPU."""
import jax.numpy as jnp
import pytest

from repro.api.experiment import Experiment, run
from repro.kernels.efe import mega as mega_kernel
from repro.kernels.efe.ops import on_tpu

from mega_helpers import (_assert_bit_equal, _assert_rollouts_match,
                          _kernel_run, _router_world, _vmem_with)


# ---------------------------------------------------------- Pallas megakernel
@pytest.mark.parametrize("slot_dtype,n_resident", [
    ("float32", 0), ("float32", 1), ("bfloat16", 0), ("bfloat16", 2)],
    ids=["f32-streamed", "f32-prefix", "bf16-streamed", "bf16-prefix"])
def test_mega_pallas_streamed_tape_matches_oracle(slot_dtype, n_resident):
    """The kernel with its tape streamed from HBM (every chunk, or past a
    resident prefix), in interpret mode: against the XLA oracle, bit-equal
    actions and <=1e-4 everywhere; against the kernel holding the whole
    tape resident in the same chunks, bit-equal throughout (same
    arithmetic, same order; a skipped unfilled chunk would add zeros).  A
    60-slot tape in 16-slot chunks ends in a 12-slot chunk, which the last
    window (t0=50) reads."""
    r, t, chunk = 3, 60, 16
    router, _ = _router_world(r, t)
    limit = _vmem_with(router.cfg, t, slot_dtype, chunk, n_resident)
    plan = mega_kernel.tape_plan(router.cfg, t, 10, jnp.dtype(slot_dtype),
                                 False, slot_chunk=chunk, vmem_limit=limit)
    assert (plan.j_res, plan.j_chunk) == (n_resident * chunk, chunk)
    base = dict(router="aif", mega=True, n_cells=r,
                n_windows=t, mega_slot_dtype=slot_dtype)
    kernel = Experiment(**base, use_pallas=True)
    streamed = _kernel_run(kernel, chunk, vmem_limit=limit)
    _assert_rollouts_match(run(Experiment(**base)), streamed)
    _assert_bit_equal(_kernel_run(kernel, chunk), streamed)


@pytest.mark.skipif(not on_tpu(), reason="compiled Pallas megakernel needs "
                    "a TPU backend (interpret-only on CPU)")
def test_mega_pallas_compiled_matches_oracle():
    """Accelerator-gated non-interpret parity (scaffolding for TPU CI)."""
    base = dict(router="aif", mega=True, n_cells=8,
                n_windows=20)
    r1 = run(Experiment(**base))
    r2 = run(Experiment(**base, use_pallas=True))
    _assert_rollouts_match(r1, r2)
