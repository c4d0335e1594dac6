"""The Pallas megakernel's slot tape: the resident fold skipping unfilled
chunks (interpret mode, against the oracle), the live-chunk rule against
the host's count, the tape plan's VMEM pricing against the launch it
makes, and the ``tape_bytes`` and ``folded_slots`` counters."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api.experiment import Experiment, run
from repro.core import mega as mega_core
from repro.envsim import batched
from repro.kernels.efe import mega as mega_kernel
from repro.kernels.efe.mega import block_vmem_bytes

from mega_helpers import (KEY, _assert_bit_equal, _assert_rollouts_match,
                          _kernel_run, _router_world, _vmem_with)



@pytest.fixture(scope="module")
def oracle_60():
    """The XLA oracle's run of three cells over 60 windows."""
    return run(Experiment(router="aif", mega=True, n_cells=3, n_windows=60))


@pytest.mark.parametrize("chunk", [16, 20, 40],
                         ids=["tail", "on-boundaries", "one-whole-chunk"])
def test_mega_pallas_resident_fold_skips_unfilled_chunks(oracle_60, chunk):
    """The kernel holding a 60-slot tape resident folds only the chunks
    that hold a filled slot (slot < t0), in interpret mode: bit-equal to
    the kernel streaming the whole tape in the same chunks, which follows
    the same rule, and bit-equal actions and <=1e-4 everywhere against the
    XLA oracle, which folds every slot.  Windows start at t0 = 0 (nothing
    folded), 10, ..., 50.  16-slot chunks: 3 whole and a 12-slot tail
    [48, 60), t0=10 inside the first chunk and t0=50 reaching the tail;
    20-slot chunks: 3 whole, t0=20 and 40 on chunk boundaries; 40-slot
    chunks: one whole chunk (its own path in the kernel), skipped at t0=0,
    and a 20-slot tail."""
    r, t = 3, 60
    router, _ = _router_world(r, t)
    limit = _vmem_with(router.cfg, t, jnp.float32, chunk, 0)
    assert (mega_kernel.tape_plan(router.cfg, t, 10, jnp.dtype(jnp.float32),
                                  False, slot_chunk=chunk).j_res == t)
    assert (mega_kernel.tape_plan(router.cfg, t, 10, jnp.dtype(jnp.float32),
                                  False, slot_chunk=chunk,
                                  vmem_limit=limit).j_res == 0)
    kernel = Experiment(router="aif", mega=True, n_cells=r,
                        n_windows=t, use_pallas=True)
    resident = _kernel_run(kernel, chunk)
    _assert_rollouts_match(oracle_60, resident)
    _assert_bit_equal(resident, _kernel_run(kernel, chunk, vmem_limit=limit))


@pytest.mark.parametrize("j,j_res", [(600, 600), (3600, 2048)],
                         ids=["r2048", "t3600"])
def test_live_chunks_agree_with_the_hosts_live_slots(j, j_res):
    """The kernel's live-chunk rule and the host's count of live slots
    agree at every t0 of both cells' tapes, for the resident prefix and
    the streamed rest."""
    router, _ = _router_world(8, 10)
    jc = mega_kernel.SLOT_CHUNK
    assert mega_kernel.tape_plan(router.cfg, j, 10, jnp.dtype(jnp.float32),
                                 False).j_res == j_res
    t0 = np.arange(j + 1)
    for start, stop in ((0, j_res), (j_res, j)):
        whole, tail_live = mega_kernel.live_chunks(
            jnp.asarray(t0, jnp.int32), start, stop, jc)
        got = (np.asarray(whole) * jc
               + np.where(tail_live, (stop - start) % jc, 0))
        want = [mega_kernel._live_slots(int(t), start, stop, jc) for t in t0]
        np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("t,n_resident", [(40, None), (60, 0), (60, 2)],
                         ids=["resident", "streamed", "prefix"])
def test_tape_plan_prices_every_block_of_the_launch(monkeypatch, t,
                                                    n_resident):
    """The VMEM a launch asks for is what its pipelined blocks (each
    double-buffered), its scratch buffers and four chunk temporaries take,
    read off the ``pallas_call`` it makes."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    r, chunk = 3, 16
    router, env_step = _router_world(r, t)
    cfg, fl = router.cfg, env_step.fluid
    limit = (mega_kernel.VMEM_LIMIT if n_resident is None
             else _vmem_with(cfg, t, jnp.float32, chunk, n_resident))
    seen = {}
    real = pl.pallas_call

    def spy(kernel, **kw):
        call = real(kernel, **kw)

        def go(*operands):
            seen.update(kw, operands=operands)
            return call(*operands)
        return go
    monkeypatch.setattr(pl, "pallas_call", spy)
    w, m, k = router.period, router.n_modalities, router.n_tiers
    f32 = jnp.float32
    sds = jax.ShapeDtypeStruct
    args = (jax.eval_shape(lambda: mega_core.init_mega_state(cfg, r, t)),
            jax.eval_shape(lambda: batched.init_fluid_state(fl.params)),
            tuple(sds(x, f32) for x in ((r, m), (r, k), (r, k), (r, k),
                                         (r, m))),
            fl.params, sds((w, r), f32), sds((w, r, k), f32), None,
            jax.eval_shape(lambda: jax.random.split(KEY, w)),
            sds((w, r, cfg.n_actions), f32), sds((), jnp.int32))
    jax.eval_shape(functools.partial(
        mega_kernel.mega_window_pallas, cfg=cfg, disc=router.resolved_disc,
        util_edges=router.resolved_util_edges,
        util_period=router.util_period, dt=fl.dt,
        scrape_every=fl.scrape_every, restart_blackout=False,
        emits_mask=False, interpret=True, slot_chunk=chunk,
        vmem_limit=limit), *args)
    plan = mega_kernel.tape_plan(cfg, t, w, jnp.dtype(f32), False,
                                 slot_chunk=chunk, vmem_limit=limit)
    pipelined = [(sp.block_shape, x.dtype)
                 for sp, x in zip(seen["in_specs"], seen["operands"])
                 if sp.block_shape is not None]
    pipelined += [(sp.block_shape, o.dtype)
                  for sp, o in zip(seen["out_specs"], seen["out_shape"])]
    scratch = sum(block_vmem_bytes(sc.shape, sc.dtype)
                  for sc in seen["scratch_shapes"]
                  if sc.memory_space == pltpu.VMEM)
    want = (2 * sum(block_vmem_bytes(sh, d) for sh, d in pipelined)
            + 4 * block_vmem_bytes((8, chunk, cfg.topology.n_states), f32)
            + scratch)
    assert plan.vmem == want
    assert seen["compiler_params"].vmem_limit_bytes == want <= limit
    assert plan.j_res == (t if n_resident is None else n_resident * chunk)
    assert bool(scratch) == (plan.j_res < t)


def test_tape_plan_refuses_only_what_cannot_fit():
    """A long tape no longer bounds the launch (only the replay capacity
    bounds the horizon); a ceiling below the other blocks and the tape
    buffers alone is refused."""
    router, _ = _router_world(8, 10)
    cfg = router.cfg
    plan = mega_kernel.tape_plan(cfg, 5000, 10, jnp.dtype(jnp.float32), False)
    assert 0 < plan.j_res < 5000 and plan.vmem <= mega_kernel.VMEM_LIMIT
    assert plan.j_res % plan.j_chunk == 0
    floor = _vmem_with(cfg, 5000, jnp.float32, mega_kernel.SLOT_CHUNK, 0)
    assert mega_kernel.tape_plan(cfg, 5000, 10, jnp.dtype(jnp.float32), False,
                                 vmem_limit=floor).j_res == 0
    with pytest.raises(ValueError, match="non-tape blocks"):
        mega_kernel.tape_plan(cfg, 5000, 10, jnp.dtype(jnp.float32), False,
                              vmem_limit=floor - 1)


def test_tape_bytes_matches_hand_count():
    """A 60-slot float32 tape, 16 slots resident and 16-slot chunks: the
    streamed part [16, 60) is two whole chunks and a 12-slot one.

    Bytes a slot: q_prev, q_next 243·4 = 972 each, qnproj|sumqn 17·4 = 68,
    coefact 20·4 = 80: 2,092 in all; the prior reads coefact, q_prev, q_next
    (2,024), the EFE q_prev, coefact, qnproj (1,120).  A window reads the
    16 resident slots once (33,472) and each live streamed slot at its 10
    ticks and 2 selecting ticks (10·2,024 + 2·1,120 = 22,480).  Live slots
    by t0: 0, 10 -> 0; 20, 30 -> 16 (one chunk); 40 -> 32; 50 -> 32 + 12
    (the short chunk [48, 60) holds slot 49).  Per row 6·33,472 +
    (16 + 16 + 32 + 44)·22,480 = 2,628,672; eight padded rows."""
    router, _ = _router_world(3, 60)
    cfg = router.cfg
    limit = _vmem_with(cfg, 60, jnp.float32, 16, 1)
    got = sum(mega_kernel.tape_bytes(cfg, 3, 60, t0, 10, jnp.float32, False,
                                     slot_chunk=16, vmem_limit=limit)
              for t0 in range(0, 60, 10))
    assert got == 8 * 2_628_672


def test_folded_slots_matches_hand_count():
    """The 60-slot float32 tape in 16-slot chunks, held whole (3 chunks and
    a 12-slot tail) or behind a 16-slot resident prefix: the same slots are
    live either way.  Live slots by t0: 0 -> 0; 10 -> 16; 20, 30 -> 32;
    40 -> 48; 50 -> 60 (the tail [48, 60) holds slot 49).  (With the
    prefix: 16 resident from t0=10 on, and the streamed 0, 0, 16, 16, 32,
    44 of :func:`test_tape_bytes_matches_hand_count`.)  188 slots, each
    folded at 10 ticks for eight padded rows."""
    router, _ = _router_world(3, 60)
    cfg = router.cfg
    for limit in (mega_kernel.VMEM_LIMIT, _vmem_with(cfg, 60, jnp.float32,
                                                     16, 1)):
        got = sum(mega_kernel.folded_slots(cfg, 3, 60, t0, 10, jnp.float32,
                                           False, slot_chunk=16,
                                           vmem_limit=limit)
                  for t0 in range(0, 60, 10))
        assert got == 8 * 10 * 188


@pytest.fixture(scope="module")
def dispatched():
    """A kernel run and an oracle run of a resident 12-slot tape (two
    windows: 10 ticks from t0=0, 2 from t0=10): each counter's change over
    each run, and the arguments of every ``run.dispatch`` span."""
    from repro import obs
    seen = []
    real = obs.span

    def spy(name, **args):
        if name == "run.dispatch":
            seen.append(args)
        return real(name, **args)
    deltas = []
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(obs, "span", spy)
        for use_pallas in (True, False):
            before = obs.counters()
            run(Experiment(router="aif", mega=True, use_pallas=use_pallas,
                           n_cells=3, n_windows=12))
            after = obs.counters()
            deltas.append({k: after[k] - before.get(k, 0) for k in after})
    return deltas, seen


def test_dispatch_counts_the_tape_bytes(dispatched):
    """``tape_bytes`` on the ``run.dispatch`` span and in the counter: a
    resident 12-slot tape read once per window, two windows, eight padded
    rows of 2,092 bytes a slot; none off the kernel."""
    deltas, seen = dispatched
    assert [d.get("tape_bytes", 0) for d in deltas] == [2 * 8 * 12 * 2_092, 0]
    assert [a.get("tape_bytes") for a in seen] == [2 * 8 * 12 * 2_092, None]


def test_dispatch_counts_the_folded_slots(dispatched):
    """``folded_slots`` on the ``run.dispatch`` span and in the counter:
    the window at t0=0 folds nothing, the one at t0=10 the tape's one
    12-slot chunk at its 2 ticks, for eight padded rows; none off the
    kernel."""
    deltas, seen = dispatched
    assert [d.get("folded_slots", 0) for d in deltas] == [12 * 2 * 8, 0]
    assert [a.get("folded_slots") for a in seen] == [12 * 2 * 8, None]
