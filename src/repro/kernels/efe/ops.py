"""Public entry of the mega engine's window: the Pallas megakernel
(:mod:`repro.kernels.efe.mega`) or its XLA oracle
(:func:`repro.core.mega.mega_window`), compiled on a TPU and in interpret
mode elsewhere.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import generative
from repro.core import mega as mega_core


def on_tpu() -> bool:
    """Whether the default backend is a TPU (compiled kernels) — elsewhere
    the Pallas kernels run in interpret mode."""
    return jax.default_backend() == "tpu"


def _auto_interpret() -> bool:
    return not on_tpu()


def mega_window(state, est, obs_carry, params,
                arrival: jnp.ndarray, hazard: jnp.ndarray,
                obs_valid: jnp.ndarray | None,
                k_env: jnp.ndarray, gumbel: jnp.ndarray, t0: jnp.ndarray, *,
                cfg: generative.AifConfig, disc, util_edges, util_period: int,
                dt: float, scrape_every: int, restart_blackout: bool,
                emits_mask: bool, use_pallas: bool = False,
                interpret: bool | None = None,
                forced_down: jnp.ndarray | None = None,
                speed: jnp.ndarray | None = None,
                row_block: tuple | None = None,
                graph=None,
                shard_axis: str | None = None):
    """One whole-window launch: W fused fast ticks of the mega engine path.

    The XLA oracle is :func:`repro.core.mega.mega_window` (the factored
    belief→EFE→sample→env tick, Python-unrolled over the window); with
    ``use_pallas`` the window runs as the Pallas megakernel
    (:mod:`repro.kernels.efe.mega`), which keeps the posterior, factored
    transition cache, preference tables and env carry resident in VMEM for
    all W ticks, and the slot tape too as far as VMEM holds it: the rest of
    the tape streams from HBM, so only the slots' replay capacity bounds
    the horizon.  Chaos (``forced_down``/``speed``), sharded (``row_block``)
    and graph windows are not ported to the kernel: with ``use_pallas``
    they raise ``ValueError`` instead of running the oracle.  Inputs/outputs
    are identical either way:

      state:     :class:`repro.core.mega.MegaFleetState`.
      est:       batched env :class:`~repro.envsim.batched.FluidState`.
      obs_carry: (raw_obs, tier_util, tier_up, tier_queue, obs_mask) tuple
        carried across windows (the *published* telemetry of the previous
        tick, which this window's first belief update consumes).
      arrival/hazard/obs_valid: (W, ...) schedule slices for this window.
      k_env:     (W,) per-tick env keys; gumbel: (W, R, A) pre-drawn policy
        noise (in-kernel categorical = argmax(logp + gumbel), bitwise equal
        to ``jax.random.categorical``).
      t0:        global tick index of the window's first tick (traced ok).

    Returns ``(state, est, obs_carry, ys)`` with ys a per-tick trace tuple
    of (action, weights, raw_obs, unstable, obs_frac, env_window).
    """
    if use_pallas:
        # The megakernel's in-VMEM env port predates the fault-injection
        # schedules, draws restart randomness at the local R (not the
        # sharded engine's draw-at-true-R row_block contract) and has no
        # lane for the graph spillover's cross-cell exchange: those windows
        # need the XLA oracle, which the caller selects with use_pallas=False.
        unported = [name for name, v in (("forced_down", forced_down),
                                         ("speed", speed),
                                         ("row_block (sharded)", row_block),
                                         ("graph", graph)) if v is not None]
        if unported:
            raise ValueError(
                f"the Pallas megakernel does not implement "
                f"{', '.join(unported)} windows; run them with "
                f"use_pallas=False (the XLA mega oracle)")
        from repro.kernels.efe import mega as mega_kernel
        if interpret is None:
            interpret = _auto_interpret()
        return mega_kernel.mega_window_pallas(
            state, est, obs_carry, params, arrival, hazard, obs_valid,
            k_env, gumbel, t0, cfg=cfg, disc=disc, util_edges=util_edges,
            util_period=util_period, dt=dt, scrape_every=scrape_every,
            restart_blackout=restart_blackout, emits_mask=emits_mask,
            interpret=interpret)
    return mega_core.mega_window(
        state, est, obs_carry, params, arrival, hazard, obs_valid,
        k_env, gumbel, t0, cfg=cfg, disc=disc, util_edges=util_edges,
        util_period=util_period, dt=dt, scrape_every=scrape_every,
        restart_blackout=restart_blackout, emits_mask=emits_mask,
        forced_down=forced_down, speed=speed, row_block=row_block,
        graph=graph, shard_axis=shard_axis)
