"""Shared helpers of the mega engine's test files: the K=2 topology, the
(per-tick reference, mega) run pair on one world, the rollout-parity
assertion, the kernel's world and VMEM ceilings, and the window-level
comparison of the Pallas megakernel with its XLA oracle."""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import engine
from repro.api import experiment as experiment_mod
from repro.api.experiment import Experiment, run
from repro.core import mega as mega_core
from repro.core.topology import Topology, default_topology, get_topology
from repro.envsim import batched
from repro.kernels.efe import mega as mega_kernel
from repro.kernels.efe import ops as efe_ops
from repro.kernels.efe.mega import block_vmem_bytes

KEY = jax.random.key(0)

TWO_TIER = Topology(tier_names=("edge", "cloud"),
                    tier_classes=("edge-medium", "server"))


def _pair(scenario="paper-burst", t=25, r=6, topology="paper-3tier",
          seed=0, **mega_kw):
    """(per-tick reference run, mega run) on the same world."""
    base = dict(router="aif", scenario=scenario, n_cells=r,
                n_windows=t, seed=seed, topology=topology)
    return (run(Experiment(**base)),
            run(Experiment(**base, mega=True, **mega_kw)))


def _assert_rollouts_match(r1, r2, atol=1e-4):
    a1, a2 = np.asarray(r1.trace.actions), np.asarray(r2.trace.actions)
    np.testing.assert_array_equal(a1, a2)
    for name in ("routing_weights", "raw_obs", "unstable", "obs_frac"):
        np.testing.assert_allclose(
            np.asarray(getattr(r1.trace, name), np.float64),
            np.asarray(getattr(r2.trace, name), np.float64),
            atol=atol, err_msg=f"trace.{name}")
    for f in r1.trace.env._fields:
        np.testing.assert_allclose(
            np.asarray(getattr(r1.trace.env, f), np.float64),
            np.asarray(getattr(r2.trace.env, f), np.float64),
            atol=atol, err_msg=f"env.{f}")
    assert np.all(np.isfinite(r2.fluid.n_requests))


def _mega_carry(**kw):
    return run(Experiment(router="aif", mega=True, **kw)).final_carry


def _router_world(r, t, scenario="paper-burst"):
    topo = default_topology()
    scfg, params, env_step = experiment_mod._build_world(
        topo, scenario, r, t, 1.0, 0)
    return experiment_mod._make_aif(topo, scfg, True, True), env_step


def _vmem_with(cfg, t, slot_dtype, chunk, n_resident):
    """The VMEM ceiling at which a launch over a ``t``-slot tape holds
    ``n_resident`` whole chunks resident and streams the rest: the other
    blocks, the two buffers of each tape operand and the resident blocks,
    all priced by hand."""
    s = cfg.topology.n_states
    widths = mega_kernel.tape_widths(cfg, slot_dtype).values()
    fixed = mega_kernel.mega_vmem_bytes(
        mega_kernel._fixed_blocks(cfg, 10, False),
        block_vmem_bytes((8, chunk, s), jnp.float32))
    buffers = sum(block_vmem_bytes((2, 8, chunk, w), d) for w, d in widths)
    per_chunk = 2 * sum(block_vmem_bytes((8, chunk, w), d) for w, d in widths)
    return fixed + buffers + n_resident * per_chunk


def _kernel_run(experiment, chunk, **kernel_kw):
    """``experiment`` through the interpret-mode kernel folding its tape in
    ``chunk``-slot chunks (``kernel_kw``: more keywords of the launch)."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(mega_kernel, "mega_window_pallas", functools.partial(
            mega_kernel.mega_window_pallas, slot_chunk=chunk, **kernel_kw))
        jax.clear_caches()
        try:
            return run(experiment)
        finally:
            jax.clear_caches()


def _assert_bit_equal(r1, r2):
    for name in ("final_carry", "trace"):
        for x, y in zip(jax.tree_util.tree_leaves(getattr(r1, name)),
                        jax.tree_util.tree_leaves(getattr(r2, name))):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                          err_msg=name)


#: The topologies the window comparison spans, by tier count.
TOPOLOGIES = {"k2": TWO_TIER, "k3": get_topology("paper-3tier"),
              "k5": get_topology("continuum-5tier")}


#: Ticks of a compared window.  The router's slow period is set to its 5 s
#: dwell, so a window has one selecting and four held ticks: half the
#: paper's, which halves what the oracle and the interpret-mode kernel
#: compile for each of the grid's shapes.
WINDOW = 5
#: Windows the oracle runs, each closed by its slow boundary, before the
#: compared one: after 60 ticks of learning the slot fold, the telemetry
#: masks and the utilization scrape each move the posterior well past the
#: comparison's 1e-4 (after two windows they barely move it).  The compared
#: window starts at t0=60, on a 10 s scrape.
ADVANCE = 12


def _windows(topo, r, scenario):
    """The oracle's fleet state after :data:`ADVANCE` windows from a fresh
    fleet, and the next window from it through the kernel and through the
    oracle: (state, [kernel out, oracle out])."""
    scfg, _, env_step = experiment_mod._build_world(
        topo, scenario, r, (ADVANCE + 1) * WINDOW, 1.0, 0)
    router = experiment_mod._make_aif(topo, scfg, False, True)
    router = dataclasses.replace(router, cfg=dataclasses.replace(
        router.cfg, slow_period_s=float(WINDOW)))
    cfg, fl, w = router.cfg, env_step.fluid, router.period
    assert (w, router.dwell) == (WINDOW, WINDOW)
    statics = dict(cfg=cfg, disc=router.resolved_disc,
                   util_edges=router.resolved_util_edges,
                   util_period=router.util_period, dt=fl.dt,
                   scrape_every=fl.scrape_every,
                   restart_blackout=fl.restart_blackout,
                   emits_mask=bool(env_step.emits_mask))

    @jax.jit
    def draws(key, t0):
        """The window's draws and slices, as the engine makes them
        (``_mega_impl``)."""
        key, (k_env, k_fast, k_slow) = engine._key_block(key, w, r)
        gumbel = jax.vmap(jax.vmap(
            lambda k: jax.random.gumbel(k, (cfg.n_actions,))))(k_fast)
        sl = [None if x is None else jax.lax.dynamic_slice_in_dim(x, t0, w)
              for x in (fl.arrival_rate, fl.hazard_scale, fl.obs_valid)]
        return key, k_slow, (*sl, k_env, gumbel, t0)

    kernel, oracle = (jax.jit(functools.partial(
        efe_ops.mega_window, use_pallas=p, **statics)) for p in (True, False))
    slow = jax.jit(functools.partial(mega_core.mega_slow_step, cfg=cfg))
    state = mega_core.init_mega_state(cfg, r, (ADVANCE + 1) * w)
    est = batched.init_fluid_state(fl.params)
    obs = engine._fresh_obs_carry(r, router.n_modalities, router.n_tiers)
    key = jax.random.key(0)
    for t0 in range(0, ADVANCE * w, w):
        key, k_slow, xs = draws(key, np.int32(t0))
        state, est, obs, _ = oracle(state, est, obs, fl.params, *xs)
        state = slow(state, k_slow[-1])
    _, _, xs = draws(key, np.int32(ADVANCE * w))
    return state, [f(state, est, obs, fl.params, *xs)
                   for f in (kernel, oracle)]


def _assert_window_matches_oracle(topology, r, scenario):
    """The kernel against the oracle on one window over a filled tape.

    The oracle runs :data:`ADVANCE` windows with their slow boundaries, so
    the tape holds filled slots and the model has learnt; the next window
    then runs through :func:`repro.kernels.efe.ops.mega_window` both ways:
    the kernel (interpret mode off a TPU) and the oracle.  Bit-equal
    actions; the window's trace, the router state (posterior, slot tape,
    cache) and the telemetry carry within 1e-4, as
    :func:`_assert_rollouts_match` holds a rollout; the env state finite.
    """
    state, (kernel, oracle) = _windows(TOPOLOGIES[topology], r, scenario)
    filled = np.asarray(state.slots.q_next, np.float64).sum(-1)
    np.testing.assert_allclose(filled[:, :ADVANCE * WINDOW], 1.0, atol=1e-4)
    np.testing.assert_array_equal(filled[:, ADVANCE * WINDOW:], 0.0)
    np.testing.assert_array_equal(np.asarray(kernel[3][0]),
                                  np.asarray(oracle[3][0]))
    for (path, a), (_, b) in zip(
            jax.tree_util.tree_flatten_with_path(kernel[:1] + kernel[2:])[0],
            jax.tree_util.tree_flatten_with_path(oracle[:1] + oracle[2:])[0]):
        np.testing.assert_allclose(np.asarray(a, np.float64),
                                   np.asarray(b, np.float64), atol=1e-4,
                                   err_msg=jax.tree_util.keystr(path))
    for leaf in jax.tree_util.tree_leaves(kernel[1]):
        assert np.all(np.isfinite(np.asarray(leaf, np.float64)))
