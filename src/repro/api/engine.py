"""Closed-loop fleet engine over the Router protocol.

One on-device program runs *any* router — the AIF agent or the pure-JAX
baseline ports (:mod:`repro.api.router`) — against a batched environment:
each of the ``n_steps`` control windows hands the previous window's
telemetry to ``router.step`` (inside the jitted ``lax.scan``, no per-tick
host callbacks), applies the returned (R, K) routing weights to the
environment, and carries the new observations forward.  This is the engine
layer the old AIF-only ``fleet_rollout`` was refactored into: the router is
a static jit argument, its state pytree is the scan carry, and the AIF
router reproduces the pre-refactor program bit-for-bit (golden test).

Scheduling comes from the router's hints: routers with a slow learning
cadence (``has_slow``) get the nested slow-period scan with
once-per-boundary :meth:`~repro.api.router.Router.slow_step`, routers with
an action dwell > 1 get held ticks dispatched to ``light_step`` (the AIF
dwell-blocking optimization); memoryless baselines compile to a flat scan.

Telemetry degradation: when the environment adapter declares
``env_step.emits_mask`` (see :func:`repro.envsim.batched.make_env_step`) —
or the caller passes ``obs_masked=True`` explicitly for wrapped closures —
each window's validity mask is carried into the next tick's ``obs_mask``
and the trace records the effective-observation fraction.  Mask-aware
routers (AIF) discount the masked evidence; mask-oblivious baselines
consume the stale re-emitted values, exactly like real pipelines.

Device sharding (:func:`sharded_rollout`): the same nested scan runs under
``jax.shard_map`` over a 1-D cell-axis mesh — router carry, env state and
per-cell PRNG keys sharded along R, randomness drawn at the device-count-
invariant true-R global shape and row-sliced per shard, and per-tick traces
replaced by an O(R/devices)-memory metrics accumulator whose reductions are
``psum``-ed across the mesh at the end.  A 1-device mesh reproduces the
unsharded engine bit-for-bit.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.api.router import Router, RouterObs
from repro.core import mega as mega_mod
from repro.core.fleet import FleetTrace
from repro.kernels.efe import ops as efe_ops


def rollout(router: Router,
            carry,
            env_state,
            env_step: Callable,
            n_steps: int,
            key: jax.Array,
            *,
            obs_masked: bool | None = None,
            t0: int | None = None,
            launch_periods: int | None = None):
    """Closed-loop fleet experiment as one on-device ``lax.scan``.

    Args:
      router: static router spec (hashable; see :class:`repro.api.router`).
      carry: the router's state pytree (``router.init_carry(r)`` or a
        previous rollout's final carry), leading cell axis R on every leaf.
      env_state: environment state pytree with leading cell dim R (e.g.
        :class:`repro.envsim.batched.FluidState`).
      env_step: ``(env_state, weights, t_idx, key) -> (env_state, info)``
        where ``info`` carries ``raw_obs`` (R, M), ``tier_utilization`` /
        ``tier_up`` / ``tier_queue`` (R, K) and ``obs_mask`` (R, M) — see
        :func:`repro.envsim.batched.make_env_step`.
      n_steps: number of control windows T (static).
      key: PRNG key driving the environment and the per-cell router keys.
      obs_masked: force (True) / suppress (False) the telemetry-mask carry;
        None auto-detects from ``env_step.emits_mask``.
      t0: fast ticks already elapsed on every cell's clock (static).  Only
        needed when ``carry`` is traced; concrete carries are introspected
        via ``router.clock_phase``.
      launch_periods: mega routers only — dispatch the super-launch in
        chunks of this many slow periods instead of one jit spanning the
        whole horizon (actions and final state bit-identical, telemetry
        floats within ulps; bounds per-launch compile scope and aligns
        with :func:`resumable_rollout` checkpoint boundaries).  None
        (default) launches the whole run at once.

    Returns:
      (final carry, final env state, :class:`~repro.core.fleet.FleetTrace`).

    ``carry`` and ``env_state`` are donated — reuse the returned states.
    """
    if getattr(router, "mega", False):
        state, est, trace, _ = _mega_rollout(
            router, carry, env_state, env_step, n_steps, key,
            obs_masked=obs_masked, t0=t0, launch_periods=launch_periods)
        return state, est, trace
    if launch_periods is not None:
        raise ValueError(
            "launch_periods only applies to mega routers (the per-tick "
            "engine is a single scan already); set mega=True or drop it")
    period = max(int(router.period), 1)
    clock_phase = (int(t0) % period if t0 is not None
                   else router.clock_phase(carry))
    if obs_masked is None:
        obs_masked = bool(getattr(env_step, "emits_mask", False))
    return _rollout_impl(carry, env_state, env_step, n_steps, key,
                         router=router, obs_masked=obs_masked,
                         clock_phase=clock_phase)


def _row_block_keys(key: jax.Array, row_start: jnp.ndarray, n_true: int,
                    n_pad: int, n_local: int) -> jax.Array:
    """This shard's block of the fleet-global per-cell key split.

    JAX PRNG outputs are a function of the requested shape (not
    prefix-stable), so per-cell keys must be split at the fixed true-R
    global count on every shard and row-sliced — that is what makes every
    device count (including 1) reproduce the unsharded engine's key stream
    exactly.  Phantom pad rows reuse the last real cell's key; their
    outputs never enter a reduction.
    """
    full = jax.random.split(key, n_true)
    if n_pad > n_true:
        full = jnp.concatenate(
            [full, jnp.repeat(full[-1:], n_pad - n_true, axis=0)])
    return jax.lax.dynamic_slice_in_dim(full, row_start, n_local)


def _key_block(key: jax.Array, n: int, r: int, rows: tuple | None = None):
    """Pre-split the engine's per-tick key chain for ``n`` ticks at once.

    The per-tick chain is ``k, k_env, k_agents = split(k, 3)`` followed by an
    R-way per-cell split and a fast/slow split per cell — 3 + R + R splits
    serialized inside every tick of the rollout scan.  Hoisting the whole
    chain into one block per slow period takes the key derivation off the
    tick's critical path; the split *tree* is unchanged, so the produced
    keys (and therefore the rollout) are bit-identical to the per-tick
    chain (pinned by ``tests/test_mega.py::test_key_block_replays_chain``).

    Returns (advanced chain key, (k_env (n,), k_fast (n, R), k_slow (n, R))).
    """
    def body(k, _):
        k, k_env, k_agents = jax.random.split(k, 3)
        if rows is None:
            keys = jax.random.split(k_agents, r)
        else:
            keys = _row_block_keys(k_agents, rows[0], rows[1], rows[2], r)
        ks = jax.vmap(jax.random.split)(keys)
        return k, (k_env, ks[:, 0], ks[:, 1])

    return jax.lax.scan(body, key, None, length=n)


@functools.partial(jax.jit,
                   static_argnames=("router", "env_step", "n_steps",
                                    "obs_masked", "clock_phase"),
                   donate_argnames=("carry0", "env_state"))
def _rollout_impl(carry0,
                  env_state,
                  env_step: Callable,
                  n_steps: int,
                  key: jax.Array,
                  *,
                  router: Router,
                  obs_masked: bool = False,
                  clock_phase: int | None = 0):
    carry, trace = _rollout_core(
        carry0, env_state, env_step, n_steps, key, router=router,
        obs_masked=obs_masked, clock_phase=clock_phase)
    return carry[0], carry[1], trace


@functools.partial(jax.jit,
                   static_argnames=("router", "env_step", "n_steps",
                                    "obs_masked", "clock_phase"),
                   donate_argnames=("carry0", "env_state"))
def _resumable_impl(carry0,
                    env_state,
                    obs_init,
                    t_begin,
                    env_step: Callable,
                    n_steps: int,
                    key: jax.Array,
                    *,
                    router: Router,
                    obs_masked: bool = False,
                    clock_phase: int | None = 0):
    """The chunked twin of :func:`_rollout_impl`: traced ``t_begin`` (so
    equal-length chunks share one compilation) plus the full telemetry
    carry in and out.  The extra snapshot output is
    ``(raw_obs, tier_util, tier_up, tier_queue, obs_mask, chain_key)``."""
    carry, trace = _rollout_core(
        carry0, env_state, env_step, n_steps, key, router=router,
        obs_masked=obs_masked, clock_phase=clock_phase,
        t_begin=t_begin, obs_init=obs_init)
    snap = (carry[2], carry[3], carry[4], carry[5], carry[6], carry[7])
    return carry[0], carry[1], trace, snap


def _rollout_core(carry0,
                  env_state,
                  env_step: Callable,
                  n_steps: int,
                  key: jax.Array,
                  *,
                  router: Router,
                  obs_masked: bool = False,
                  clock_phase: int | None = 0,
                  rows: tuple | None = None,
                  reducer=None,
                  stats0=(),
                  t_begin=None,
                  obs_init=None):
    """Shared scan core of the (un)sharded rollouts.

    ``rows = (row_start, n_true, n_pad)`` switches the per-cell key split to
    the fleet-global draw-and-slice mode (see :func:`_row_block_keys`);
    ``reducer`` replaces the stacked per-tick :class:`FleetTrace` with an
    O(cells)-memory accumulator (``stats0`` its initial value) — the trace
    output is then an empty pytree.  With both at their defaults this is
    exactly the pre-shard engine program, bit for bit.

    Resumable chunks: ``t_begin`` (traced scalar, None = the literal fresh
    program) offsets every window index — schedules, scrape clock and
    router ``t_idx`` all see global time — and ``obs_init`` replaces the
    fresh zeros/ones telemetry carry with a snapshot's
    ``(raw_obs, tier_util, tier_up, tier_queue, obs_mask)``.  Because the
    per-tick key chain folds forward from ``key`` and the slow schedule is
    phase-aligned by the caller, a chunked run replays the uninterrupted
    op sequence exactly.

    Returns (full scan carry, trace) — carry[0] router state, carry[1] env
    state, carry[-1] reducer stats, carry[2:7] the telemetry carry,
    carry[7] the advanced chain key.
    """
    r = jax.tree_util.tree_leaves(env_state)[0].shape[0]
    k_tiers = router.n_tiers
    m = router.n_modalities
    period = max(int(router.period), 1)
    dwell = max(int(router.dwell), 1)
    # Dwell blocking: on ticks with t % dwell != 0 the selected action is
    # pinned, so the router's selection work (for AIF: the EFE launch
    # streaming the full (R, A, S, S) cached B) is dispatched to the cheap
    # light_step.  Requires the fleet clock phase to be known and — for
    # routers with a slow cadence — the dwell pattern to be static within a
    # period; without a slow cadence the period is irrelevant.
    dwell_blocked = (dwell > 1 and clock_phase is not None
                     and (not router.has_slow or period % dwell == 0))
    # Mask-emitting environments feed each window's telemetry-validity mask
    # into the next tick; otherwise the mask stays an untouched all-ones
    # carry and every step runs the mask-free path.  (Resolved statically in
    # rollout(): env_step.emits_mask or an explicit obs_masked=.)
    emits_mask = obs_masked

    def tick_core(carry, t_idx, k_env, k_fast, k_slow, light: bool):
        (rst, est, raw_obs, tier_util, tier_up, tier_queue, obs_mask, k, _,
         stats) = carry
        obs = RouterObs(raw_obs=raw_obs, tier_utilization=tier_util,
                        tier_up=tier_up, tier_queue=tier_queue, t_idx=t_idx)
        mask = obs_mask if emits_mask else None
        if light:
            rst, weights, tinfo = router.light_step(rst, obs, mask)
        else:
            rst, weights, tinfo = router.step(rst, obs, mask, k_fast)
        est, win = env_step(est, weights, t_idx, k_env)
        next_mask = win.obs_mask if emits_mask else obs_mask
        ys = FleetTrace(actions=tinfo.action,
                        routing_weights=weights,
                        raw_obs=raw_obs,
                        unstable=tinfo.unstable,
                        obs_frac=jnp.mean(obs_mask, axis=-1),
                        env=win,
                        watchdog=tinfo.watchdog)
        if reducer is not None:
            stats = reducer.update(stats, t_idx, ys)
            ys = ()
        return (rst, est, win.raw_obs, win.tier_utilization, win.tier_up,
                win.tier_queue, next_mask, k, k_slow, stats), ys

    def tick_body(carry, t_idx, light: bool):
        # Per-tick key chain — flat scans only; the nested slow-period path
        # consumes pre-split blocks from _key_block instead (same tree).
        k, k_env, k_agents = jax.random.split(carry[7], 3)
        if rows is None:
            keys = jax.random.split(k_agents, r)
        else:
            keys = _row_block_keys(k_agents, rows[0], rows[1], rows[2], r)
        ks = jax.vmap(jax.random.split)(keys)          # (R, 2) keys
        carry = carry[:7] + (k,) + carry[8:]
        return tick_core(carry, t_idx, k_env, ks[:, 0], ks[:, 1], light)

    def full_body(carry, t_idx):
        return tick_body(carry, t_idx, light=False)

    def light_body(carry, t_idx):
        return tick_body(carry, t_idx, light=True)

    def full_xs(carry, xs):
        return tick_core(carry, *xs, light=False)

    def light_xs(carry, xs):
        return tick_core(carry, *xs, light=True)

    def dwell_block(carry, t_start, n_light: int, keys3=None):
        """One dwell block: a selecting tick, then n_light held ticks."""
        if keys3 is None:
            carry, y0 = full_body(carry, t_start)
        else:
            carry, y0 = full_xs(carry,
                                (t_start,) + tuple(a[0] for a in keys3))
        y0 = jax.tree_util.tree_map(lambda a: a[None], y0)
        if not n_light:
            return carry, y0
        ts = t_start + 1 + jnp.arange(n_light, dtype=jnp.int32)
        if keys3 is None:
            carry, ys = jax.lax.scan(light_body, carry, ts)
        else:
            carry, ys = jax.lax.scan(
                light_xs, carry, (ts,) + tuple(a[1:] for a in keys3))
        return carry, jax.tree_util.tree_map(
            lambda a, b: jnp.concatenate([a, b], axis=0), y0, ys)

    def run_ticks(carry, t_start, n: int, phase: int = 0,
                  hoisted: bool = False):
        """n consecutive ticks starting at traced window index ``t_start``,
        whose first tick sits at dwell offset ``phase`` on the fleet clock
        (static).  Misaligned heads run as held ticks until the next dwell
        boundary; then selecting-tick-led blocks.  ``hoisted`` pre-splits
        the whole key block for the n ticks up front (the slow-period path:
        n <= period, so the block stays a few-KB (n, R) key array)."""
        keys3 = None
        if hoisted and n:
            k, keys3 = _key_block(carry[7], n, r, rows)
            carry = carry[:7] + (k,) + carry[8:]
        outs = []
        if dwell_blocked and n:
            head = min((dwell - phase) % dwell, n)
            if head:
                ts = t_start + jnp.arange(head, dtype=jnp.int32)
                if keys3 is None:
                    carry, ys = jax.lax.scan(light_body, carry, ts)
                else:
                    carry, ys = jax.lax.scan(
                        light_xs, carry,
                        (ts,) + tuple(a[:head] for a in keys3))
                outs.append(ys)
            t_start = t_start + head
            n_blocks, tail = divmod(n - head, dwell)
            if n_blocks:
                tb = t_start + dwell * jnp.arange(n_blocks, dtype=jnp.int32)
                if keys3 is None:
                    def block_body(c, t):
                        return dwell_block(c, t, dwell - 1)
                    carry, ys = jax.lax.scan(block_body, carry, tb)
                else:
                    blk = tuple(
                        a[head:head + n_blocks * dwell].reshape(
                            (n_blocks, dwell) + a.shape[1:])
                        for a in keys3)

                    def block_body(c, xs):
                        t, ke, kf, ksl = xs
                        return dwell_block(c, t, dwell - 1,
                                           keys3=(ke, kf, ksl))
                    carry, ys = jax.lax.scan(block_body, carry, (tb,) + blk)
                outs.append(jax.tree_util.tree_map(
                    lambda x: x.reshape((n_blocks * dwell,) + x.shape[2:]),
                    ys))
            if tail:
                k3 = (None if keys3 is None else
                      tuple(a[head + n_blocks * dwell:] for a in keys3))
                carry, ys = dwell_block(carry, t_start + n_blocks * dwell,
                                        tail - 1, keys3=k3)
                outs.append(ys)
        else:
            ts = t_start + jnp.arange(n, dtype=jnp.int32)
            if keys3 is None:
                carry, ys = jax.lax.scan(full_body, carry, ts)
            else:
                carry, ys = jax.lax.scan(full_xs, carry, (ts,) + keys3)
            outs.append(ys)
        if len(outs) == 1:
            return carry, outs[0]
        return carry, jax.tree_util.tree_map(
            lambda *xs: jnp.concatenate(xs, axis=0), *outs)

    def slow_after(carry):
        rst, est, raw_obs, tier_util, tier_up, tier_queue, obs_mask, k, \
            k_slow, stats = carry
        # Slow learning once per period, with the boundary tick's slow key —
        # not recomputed-and-discarded on the intermediate ticks.
        rst = router.slow_step(rst, k_slow)
        return (rst, est, raw_obs, tier_util, tier_up, tier_queue, obs_mask,
                k, k_slow, stats)

    if obs_init is None:
        obs0 = jnp.zeros((r, m), jnp.float32)
        util0 = jnp.zeros((r, k_tiers), jnp.float32)
        up0 = jnp.ones((r, k_tiers), jnp.float32)
        queue0 = jnp.zeros((r, k_tiers), jnp.float32)
        mask0 = jnp.ones((r, m), jnp.float32)
    else:
        obs0, util0, up0, queue0, mask0 = obs_init
    # the fresh/resumed first window index; kept a Python literal on the
    # fresh path so the pre-resume program is byte-identical
    t00 = (jnp.asarray(0, jnp.int32) if t_begin is None
           else jnp.asarray(t_begin, jnp.int32))
    k_slow0 = jax.random.split(key, r)   # dummy; overwritten every tick
    if rows is not None:
        # under shard_map every per-cell carry varies across the mesh (as
        # the shard's row offset does); the scan needs the initial carry
        # typed that way too
        (carry0, env_state, obs0, util0, up0, queue0, mask0, k_slow0,
         stats0) = _vary((carry0, env_state, obs0, util0, up0, queue0,
                          mask0, k_slow0, stats0), jax.typeof(rows[0]).vma)
    carry = (carry0, env_state, obs0, util0, up0, queue0, mask0, key, k_slow0,
             stats0)
    traces = []

    if not router.has_slow:
        # Memoryless-of-slow-cadence routers (all the baselines): one flat
        # (dwell-aware) scan, no slow boundaries to respect.
        phase = (clock_phase or 0) % dwell
        carry, ys = run_ticks(carry, t00, n_steps, phase=phase)
        return carry, ys

    if clock_phase is None:
        # Mixed router clocks: flat per-tick scan, per-router slow gating
        # every tick (the pre-nesting reference schedule).
        def safe_body(c, t_idx):
            c, ys = full_body(c, t_idx)
            return slow_after(c), ys

        ts = jnp.arange(n_steps, dtype=jnp.int32)
        if t_begin is not None:
            ts = ts + t00
        carry, ys = jax.lax.scan(safe_body, carry, ts)
        return carry, ys

    # Lead-in up to the next slow boundary (empty for fresh fleets).
    lead = (-clock_phase) % period
    lead_eff = min(lead, n_steps)
    if lead_eff:
        carry, ys = run_ticks(carry, t00, lead_eff,
                              phase=clock_phase % dwell, hoisted=True)
        traces.append(ys)
        if lead_eff == lead:    # the boundary tick ran -> learn once
            carry = slow_after(carry)
    n_periods, n_rem = divmod(n_steps - lead_eff, period)

    def period_body(carry, p_idx):
        t_start = lead_eff + p_idx * period
        if t_begin is not None:
            t_start = t_start + t00
        carry, ys = run_ticks(carry, t_start, period, hoisted=True)
        return slow_after(carry), ys

    if n_periods:
        carry, ys = jax.lax.scan(
            period_body, carry, jnp.arange(n_periods, dtype=jnp.int32))
        traces.append(jax.tree_util.tree_map(
            lambda x: x.reshape((n_periods * period,) + x.shape[2:]), ys))
    if n_rem or not traces:
        t_tail = jnp.asarray(lead_eff + n_periods * period, jnp.int32)
        if t_begin is not None:
            t_tail = t_tail + t00
        carry, ys = run_ticks(carry, t_tail, n_rem, hoisted=True)
        traces.append(ys)
    trace = traces[0] if len(traces) == 1 else jax.tree_util.tree_map(
        lambda *xs: jnp.concatenate(xs, axis=0), *traces)
    return carry, trace


# ------------------------------------------------------------ megakernel path
def _mega_rollout(router, carry, env_state, env_step: Callable, n_steps: int,
                  key: jax.Array, *, obs_masked: bool | None,
                  t0: int | None, t_begin: int = 0, state_in=None,
                  obs_carry=None, n_total: int | None = None,
                  launch_periods: int | None = None):
    """Whole-window engine path (``router.mega``).

    One launch per rollout (or per ``launch_periods`` chunk): the router
    carry is the factored :class:`repro.core.mega.MegaFleetState` (slots +
    derived cache, no dense B on the hot path), the key chain is pre-split
    (:func:`_key_block` — same tree as the per-tick engine, so the
    environment and sampling randomness match it bit-for-bit) and the env
    advances *inside* the fused window.  Requires the env adapter's
    ``.fluid`` ingredients (:func:`repro.envsim.batched.make_env_step`).

    Slots are indexed by global tick, so a run either starts on a fresh
    fleet clock or *promotes* a warm dense
    :class:`~repro.core.agent.AgentState` (a per-tick engine carry whose
    uniform clock sits on a slow-period/dwell boundary) onto the mega path
    via :func:`repro.core.mega.init_mega_state`'s ``from_agent_state`` —
    the env schedules are then indexed globally (same world), i.e. they
    must cover ``[t_warm, t_warm + n_steps)``.
    """
    fl = getattr(env_step, "fluid", None)
    if fl is None:
        raise ValueError(
            "mega rollouts need the env adapter's whole-window ingredients "
            "(env_step.fluid, set by repro.envsim.batched.make_env_step) — "
            "a wrapped per-tick closure cannot be fused into the window; "
            "rebuild the adapter or set mega=False")
    if n_steps <= 0:
        raise ValueError("mega rollouts need n_steps >= 1")
    if t0 not in (None, 0):
        raise ValueError(
            f"mega rollouts start on a fresh fleet clock (t0=0), got "
            f"t0={t0}: transition slots are indexed by the global tick")
    period = max(int(router.period), 1)
    t = getattr(carry, "t", None)
    warm = 0
    if t is not None:
        if isinstance(t, jax.core.Tracer):
            raise ValueError(
                "mega rollouts cannot resume from a traced carry — pass "
                "carry=None (or a fresh init_carry) outside jit")
        t_np = np.asarray(t)
        if t_np.size and np.any(t_np != 0):
            if isinstance(carry, mega_mod.MegaFleetState):
                raise ValueError(
                    "a warm MegaFleetState cannot seed a new rollout (its "
                    "slots were sized for the previous horizon) — densify "
                    "it with repro.core.mega.to_agent_state and pass the "
                    "dense carry; it will be re-promoted at the new size")
            # dense per-tick carry -> promote onto the mega path mid-life
            vals = np.unique(t_np)
            if vals.size != 1:
                raise ValueError(
                    "warm mega promotion needs a uniform fleet clock; got "
                    f"t in {vals[:8]}")
            warm = int(vals[0])
            dwell = max(int(router.dwell), 1)
            if warm % period or warm % dwell:
                raise ValueError(
                    f"warm mega promotion must start on a slow-period and "
                    f"dwell boundary (t % {period} == 0 and % {dwell} == "
                    f"0), got t={warm}")
            if router.use_pallas:
                raise ValueError(
                    "warm-promoted fleets run the XLA oracle window (the "
                    "Pallas megakernel's factored operands assume the "
                    "fresh sticky transition prior, not a promoted dense "
                    "baseline) — set use_pallas=False for mega "
                    "continuation runs")
            if t_begin:
                raise ValueError("warm promotion and a resumable t_begin "
                                 "cannot be combined")
            t_begin = warm
    if obs_masked is None:
        obs_masked = bool(getattr(env_step, "emits_mask", False))
    cfg = router.cfg
    r = jax.tree_util.tree_leaves(env_state)[0].shape[0]
    if state_in is None:
        # slots are indexed by global tick, so a chunked run must size them
        # to the *whole* horizon up front (n_total), not this chunk's —
        # and a promoted run to the warm prefix plus its remaining horizon
        slot_dtype = (jnp.bfloat16 if router.mega_slot_dtype == "bfloat16"
                      else jnp.float32)
        horizon = warm + (n_total if n_total is not None else n_steps)
        state_in = mega_mod.init_mega_state(
            cfg, r, horizon, slot_dtype=slot_dtype,
            from_agent_state=(carry if warm else None))
    if warm and fl.arrival_rate.shape[0] < warm + n_steps:
        raise ValueError(
            f"warm mega promotion indexes the env schedules globally (same "
            f"world): need at least {warm + n_steps} scheduled ticks, got "
            f"{fl.arrival_rate.shape[0]} — build the env_step over the "
            f"full-run schedules")
    if obs_carry is None:
        m, k_tiers = router.n_modalities, router.n_tiers
        obs_carry = (jnp.zeros((r, m), jnp.float32),
                     jnp.zeros((r, k_tiers), jnp.float32),
                     jnp.ones((r, k_tiers), jnp.float32),
                     jnp.zeros((r, k_tiers), jnp.float32),
                     jnp.ones((r, m), jnp.float32))

    def launch(state, est, obs, k, tb, n):
        return _mega_impl(
            state, est, obs, fl.params, fl.arrival_rate, fl.hazard_scale,
            fl.obs_valid, fl.forced_down, fl.speed, fl.graph, k,
            jnp.asarray(tb, jnp.int32), router=router, n_steps=n,
            obs_masked=obs_masked, dt=fl.dt, scrape_every=fl.scrape_every,
            restart_blackout=fl.restart_blackout)

    if launch_periods is None:
        return launch(state_in, env_state, obs_carry, key, t_begin, n_steps)
    if int(launch_periods) < 1:
        raise ValueError(f"launch_periods must be >= 1, got {launch_periods}")
    # chunked super-launch: same windows, same key chain, same slot indices
    # — only the host-side dispatch granularity changes.  Actions and the
    # final factored state are bit-identical to the single launch (the
    # chain key and telemetry carry thread through each launch's snapshot);
    # recorded raw-telemetry floats can drift by ulps, since each chunk
    # shape compiles its own XLA program with different fusion.
    chunk = int(launch_periods) * period
    state, est, obs, k = state_in, env_state, obs_carry, key
    traces, c0 = [], 0
    while c0 < n_steps:
        n = min(chunk, n_steps - c0)
        state, est, tr, (obs, k) = launch(state, est, obs, k,
                                          t_begin + c0, n)
        traces.append(tr)
        c0 += n
    trace = (traces[0] if len(traces) == 1 else jax.tree_util.tree_map(
        lambda *xs: jnp.concatenate(xs, axis=0), *traces))
    return state, est, trace, (obs, k)


@functools.partial(jax.jit,
                   static_argnames=("router", "n_steps", "obs_masked", "dt",
                                    "scrape_every", "restart_blackout"),
                   donate_argnames=("state", "env_state"))
def _mega_impl(state,
               env_state,
               obs_carry,
               params,
               arrival: jnp.ndarray,
               hazard: jnp.ndarray,
               obs_valid: jnp.ndarray | None,
               forced_down: jnp.ndarray | None,
               speed: jnp.ndarray | None,
               graph,
               key: jax.Array,
               t_begin: jnp.ndarray,
               *,
               router,
               n_steps: int,
               obs_masked: bool,
               dt: float,
               scrape_every: int,
               restart_blackout: bool):
    cfg = router.cfg
    r = jax.tree_util.tree_leaves(env_state)[0].shape[0]
    a_n = cfg.n_actions
    period = max(int(router.period), 1)
    statics = dict(cfg=cfg, disc=router.resolved_disc,
                   util_edges=router.resolved_util_edges,
                   util_period=router.util_period, dt=dt,
                   scrape_every=scrape_every,
                   restart_blackout=restart_blackout,
                   emits_mask=obs_masked, use_pallas=router.use_pallas)

    def window(carry, t_start, w_ticks: int, do_slow: bool):
        state, est, obs, k = carry
        k, (k_env, k_fast, k_slow) = _key_block(k, w_ticks, r)
        gum = jax.vmap(jax.vmap(
            lambda kk: jax.random.gumbel(kk, (a_n,))))(k_fast)
        arr_w = jax.lax.dynamic_slice_in_dim(arrival, t_start, w_ticks)
        haz_w = jax.lax.dynamic_slice_in_dim(hazard, t_start, w_ticks)
        ov_w = (None if obs_valid is None
                else jax.lax.dynamic_slice_in_dim(obs_valid, t_start,
                                                  w_ticks))
        fd_w = (None if forced_down is None
                else jax.lax.dynamic_slice_in_dim(forced_down, t_start,
                                                  w_ticks))
        sp_w = (None if speed is None
                else jax.lax.dynamic_slice_in_dim(speed, t_start, w_ticks))
        state, est, obs, ys = efe_ops.mega_window(
            state, est, obs, params, arr_w, haz_w, ov_w, k_env, gum,
            jnp.asarray(t_start, jnp.int32), forced_down=fd_w, speed=sp_w,
            graph=graph, **statics)
        if do_slow:
            # the boundary tick's per-cell slow keys, as in the per-tick
            # engine's slow_after
            state = mega_mod.mega_slow_step(state, k_slow[-1], cfg)
        # numerical watchdog at window granularity: quarantine-and-reinit
        # diverged cells so the next window starts from priors
        ev = jnp.zeros((w_ticks, r), jnp.float32)
        if getattr(cfg, "watchdog", False):
            bad = mega_mod.mega_watchdog_bad(state)
            state = jax.lax.cond(
                jnp.any(bad),
                lambda s: mega_mod.mega_quarantine(s, bad, cfg),
                lambda s: s, state)
            ev = ev.at[-1].set(bad.astype(jnp.float32))
        return (state, est, obs, k), ys + (ev,)

    carry = (state, env_state, obs_carry, key)
    n_periods, n_rem = divmod(n_steps, period)
    traces = []
    if n_periods:
        def period_body(c, p_idx):
            return window(c, t_begin + p_idx * period, period, do_slow=True)

        carry, ys = jax.lax.scan(period_body, carry,
                                 jnp.arange(n_periods, dtype=jnp.int32))
        traces.append(jax.tree_util.tree_map(
            lambda x: x.reshape((n_periods * period,) + x.shape[2:]), ys))
    if n_rem:
        carry, ys = window(carry, t_begin + n_periods * period, n_rem,
                           do_slow=False)
        traces.append(ys)
    ys = traces[0] if len(traces) == 1 else jax.tree_util.tree_map(
        lambda *xs: jnp.concatenate(xs, axis=0), *traces)
    state, est, obs, k = carry
    actions, weights, raw_obs, unstable, obs_frac, win, wd = ys
    trace = FleetTrace(actions=actions, routing_weights=weights,
                       raw_obs=raw_obs, unstable=unstable,
                       obs_frac=obs_frac, env=win, watchdog=wd)
    return state, est, trace, (obs, k)


# ------------------------------------------------------------- device sharding
def sharded_rollout(router: Router,
                    env_state,
                    env_step: Callable,
                    n_steps: int,
                    key: jax.Array,
                    *,
                    shard,
                    n_cells: int,
                    reducer,
                    obs_masked: bool | None = None):
    """:func:`rollout` under ``shard_map`` over a 1-D cell-axis mesh.

    The fleet's R cells are independent until the final metric reduction, so
    the whole nested scan runs per-shard: the router carry is initialized
    *inside* the shard at R/devices cells, the env state arrives sharded
    along its leading axis, and the environment closure is handed this
    shard's ``row_block`` so it slices its closed-over (T, R) schedules and
    draws restart randomness at the device-count-invariant global shape.
    Per-tick traces are replaced by the ``reducer``'s O(cells)-memory
    accumulator whose reductions are ``psum``-ed across the mesh — trace
    memory never exceeds O(R/devices).

    ``mega`` routers run the whole-window super-launch per shard
    (:func:`_sharded_mega_impl`): same key-block contract, with the
    reducer consuming each fused window's stacked trace at once
    (``reducer.update_window``).  A 1-device mesh is bit-identical to the
    unsharded mega engine.

    Args:
      router: static router spec; ``init_carry`` must be deterministic in
        its cell count (all in-repo routers are — zeros / broadcast priors).
      env_state: environment pytree **padded** to the spec's device multiple
        (leading dim ``shard.padded(n_cells)[0]`` on every leaf; see
        :func:`repro.envsim.scenarios.pad_scenario`).
      env_step: a shard-aware adapter (``env_step.supports_shard``), e.g.
        :func:`repro.envsim.batched.make_env_step`.
      n_steps: horizon T (static).
      key: fleet-global PRNG key — replicated, every shard draws the same
        global stream and row-slices it, so results are invariant to the
        device count.
      shard: a :class:`repro.api.shard.ShardSpec`.
      n_cells: *true* fleet size R (pre-padding; static).
      reducer: hashable metrics accumulator with ``init(r_local, row0)``,
        ``update(stats, t_idx, trace_tick)`` and ``finalize(stats,
        axis_name)`` (psum inside) — see
        :class:`repro.api.experiment.FleetMetricsReducer`.
      obs_masked: as in :func:`rollout`.

    Returns:
      (final router carry, final env state, reduced stats pytree) — the
      carry and env state gathered along the padded cell axis, the stats
      replicated.  On a 1-device mesh the carry and env state are
      bit-identical to the unsharded engine's.
    """
    if not getattr(env_step, "supports_shard", False):
        raise ValueError(
            "env_step does not advertise supports_shard=True — sharded "
            "rollouts need a row_block-aware adapter (see "
            "repro.envsim.batched.make_env_step); wrap or rebuild the "
            "closure instead of sharding a schedule-blind one")
    r_pad, _ = shard.padded(n_cells)
    lead = jax.tree_util.tree_leaves(env_state)[0].shape[0]
    if lead != r_pad:
        raise ValueError(
            f"env_state leading dim {lead} != padded fleet size {r_pad} "
            f"(R={n_cells} on {shard.n_devices()} devices) — build the "
            "world at true R, then pad (scenarios.pad_scenario + params at "
            "the padded size)")
    if obs_masked is None:
        obs_masked = bool(getattr(env_step, "emits_mask", False))
    if getattr(router, "mega", False):
        # super-launch per shard: the whole-window engine runs inside the
        # shard_map body with this shard's row_block, so the PRNG block and
        # env randomness stay device-count-invariant (draw-at-true-R)
        if getattr(env_step, "fluid", None) is None:
            raise ValueError(
                "sharded mega rollouts need the env adapter's whole-window "
                "ingredients (env_step.fluid, set by "
                "repro.envsim.batched.make_env_step)")
        if n_steps <= 0:
            raise ValueError("mega rollouts need n_steps >= 1")
        fl = env_step.fluid
        return _sharded_mega_impl(
            env_state, key, fl.params, fl.arrival_rate, fl.hazard_scale,
            fl.obs_valid, fl.forced_down, fl.speed, fl.graph, router=router,
            n_steps=n_steps, obs_masked=obs_masked, spec=shard,
            n_cells=n_cells, reducer=reducer, dt=fl.dt,
            scrape_every=fl.scrape_every,
            restart_blackout=fl.restart_blackout)
    clock_phase = router.clock_phase(router.init_carry(1))
    return _sharded_impl(env_state, key, router=router, env_step=env_step,
                         n_steps=n_steps, obs_masked=obs_masked,
                         clock_phase=clock_phase, spec=shard,
                         n_cells=n_cells, reducer=reducer)


@functools.partial(jax.jit,
                   static_argnames=("router", "env_step", "n_steps",
                                    "obs_masked", "clock_phase", "spec",
                                    "n_cells", "reducer"),
                   donate_argnames=("env_state",))
def _sharded_impl(env_state,
                  key: jax.Array,
                  *,
                  router: Router,
                  env_step: Callable,
                  n_steps: int,
                  obs_masked: bool,
                  clock_phase: int | None,
                  spec,
                  n_cells: int,
                  reducer):
    mesh = spec.build_mesh()
    r_pad, r_local = spec.padded(n_cells)
    axis = spec.axis

    def body(est, k):
        row0 = jax.lax.axis_index(axis) * r_local
        carry0 = router.init_carry(r_local)
        # graph worlds need the mesh axis for the cross-shard spill exchange
        # (gated so custom row_block-aware closures keep their signature)
        env_kw = ({"shard_axis": axis}
                  if getattr(env_step, "has_graph", False) else {})

        def env_local(s, w, t, kk):
            return env_step(s, w, t, kk, row_block=(row0, n_cells, r_pad),
                            **env_kw)

        stats0 = reducer.init(r_local, row0)
        carry, _ = _rollout_core(
            carry0, est, env_local, n_steps, k, router=router,
            obs_masked=obs_masked, clock_phase=clock_phase,
            rows=(row0, n_cells, r_pad), reducer=reducer, stats0=stats0)
        return carry[0], carry[1], reducer.finalize(carry[-1], axis)

    return jax.shard_map(body, mesh=mesh,
                         in_specs=(P(axis), P()),
                         out_specs=(P(axis), P(axis), P()))(env_state, key)


@functools.partial(jax.jit,
                   static_argnames=("router", "n_steps", "obs_masked",
                                    "spec", "n_cells", "reducer", "dt",
                                    "scrape_every", "restart_blackout"),
                   donate_argnames=("env_state",))
def _sharded_mega_impl(env_state,
                       key: jax.Array,
                       params,
                       arrival: jnp.ndarray,
                       hazard: jnp.ndarray,
                       obs_valid: jnp.ndarray | None,
                       forced_down: jnp.ndarray | None,
                       speed: jnp.ndarray | None,
                       graph,
                       *,
                       router: Router,
                       n_steps: int,
                       obs_masked: bool,
                       spec,
                       n_cells: int,
                       reducer,
                       dt: float,
                       scrape_every: int,
                       restart_blackout: bool):
    """:func:`_mega_impl` under ``shard_map`` (the sharded super-launch).

    Each shard runs the whole-window engine over its R/devices rows: the
    :class:`~repro.core.mega.MegaFleetState` is initialized inside the
    shard, the per-period key block is drawn at the true-R global shape and
    row-sliced (:func:`_key_block` with ``rows``), and the env schedules —
    replicated operands, same operand-ness as :func:`_mega_impl` so XLA
    compiles the same arithmetic — are time-sliced here and row-sliced
    inside :func:`repro.envsim.batched.fluid_window_step` via the window's
    ``row_block``.  Instead of stacking per-tick traces, each fused
    window's (W, ...) trace is folded into the reducer at once
    (``reducer.update_window``), keeping trace memory O(R/devices).
    """
    mesh = spec.build_mesh()
    r_pad, r_local = spec.padded(n_cells)
    axis = spec.axis
    cfg = router.cfg
    a_n = cfg.n_actions
    period = max(int(router.period), 1)
    slot_dtype = (jnp.bfloat16 if router.mega_slot_dtype == "bfloat16"
                  else jnp.float32)
    statics = dict(cfg=cfg, disc=router.resolved_disc,
                   util_edges=router.resolved_util_edges,
                   util_period=router.util_period, dt=dt,
                   scrape_every=scrape_every,
                   restart_blackout=restart_blackout,
                   emits_mask=obs_masked, use_pallas=router.use_pallas)

    def body(est, k, params, arrival, hazard, obs_valid, forced_down, speed,
             graph):
        row0 = jax.lax.axis_index(axis) * r_local
        rows = (row0, n_cells, r_pad)
        state0 = mega_mod.init_mega_state(cfg, r_local, n_steps,
                                          slot_dtype=slot_dtype)
        obs0 = _fresh_obs_carry(r_local, router.n_modalities, router.n_tiers)
        stats0 = reducer.init(r_local, row0)

        def window(carry, t_start, w_ticks: int, do_slow: bool):
            state, est, obs, k, stats = carry
            k, (k_env, k_fast, k_slow) = _key_block(k, w_ticks, r_local,
                                                    rows)
            gum = jax.vmap(jax.vmap(
                lambda kk: jax.random.gumbel(kk, (a_n,))))(k_fast)
            arr_w = jax.lax.dynamic_slice_in_dim(arrival, t_start, w_ticks)
            haz_w = jax.lax.dynamic_slice_in_dim(hazard, t_start, w_ticks)
            ov_w = (None if obs_valid is None
                    else jax.lax.dynamic_slice_in_dim(obs_valid, t_start,
                                                      w_ticks))
            fd_w = (None if forced_down is None
                    else jax.lax.dynamic_slice_in_dim(forced_down,
                                                      t_start, w_ticks))
            sp_w = (None if speed is None
                    else jax.lax.dynamic_slice_in_dim(speed, t_start,
                                                      w_ticks))
            state, est, obs, ys = efe_ops.mega_window(
                state, est, obs, params, arr_w, haz_w, ov_w, k_env, gum,
                jnp.asarray(t_start, jnp.int32), forced_down=fd_w,
                speed=sp_w, row_block=rows, graph=graph, shard_axis=axis,
                **statics)
            if do_slow:
                state = mega_mod.mega_slow_step(state, k_slow[-1], cfg)
            ev = jnp.zeros((w_ticks, r_local), jnp.float32)
            if getattr(cfg, "watchdog", False):
                bad = mega_mod.mega_watchdog_bad(state)
                state = jax.lax.cond(
                    jnp.any(bad),
                    lambda s: mega_mod.mega_quarantine(s, bad, cfg),
                    lambda s: s, state)
                ev = ev.at[-1].set(bad.astype(jnp.float32))
            actions, weights, raw_obs, unstable, obs_frac, win = ys
            tr = FleetTrace(actions=actions, routing_weights=weights,
                            raw_obs=raw_obs, unstable=unstable,
                            obs_frac=obs_frac, env=win, watchdog=ev)
            stats = reducer.update_window(stats, t_start, tr)
            return (state, est, obs, k, stats)

        # the per-shard carry varies across the mesh from the first window
        # on; the scan needs it typed that way from the start
        carry = (*_vary((state0, est, obs0), (axis,)), k,
                 _vary(stats0, (axis,)))
        n_periods, n_rem = divmod(n_steps, period)
        if n_periods:
            def period_body(c, p_idx):
                return window(c, p_idx * period, period, do_slow=True), None

            carry, _ = jax.lax.scan(period_body, carry,
                                    jnp.arange(n_periods, dtype=jnp.int32))
        if n_rem:
            carry = window(carry, jnp.asarray(n_periods * period, jnp.int32),
                           n_rem, do_slow=False)
        state, est_out, _, _, stats = carry
        return state, est_out, reducer.finalize(stats, axis)

    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(axis), P(), P(), P(), P(), P(), P(), P(), P()),
        out_specs=(P(axis), P(axis), P()))(
            env_state, key, params, arrival, hazard, obs_valid, forced_down,
            speed, graph)


def _vary(tree, axes):
    """Type every leaf of a shard-local carry as varying over the mesh
    ``axes`` (shard_map's scan and cond need carries that agree on it)."""
    def cast(x):
        missing = tuple(a for a in axes if a not in jax.typeof(x).vma)
        return jax.lax.pcast(x, missing, to="varying") if missing else x
    return jax.tree_util.tree_map(cast, tree)


# ------------------------------------------------------- checkpointed chunking
def _advance_chain_key(key: jax.Array, n: int) -> jax.Array:
    """The engine's tick-chain key after ``n`` ticks.

    Every engine tick folds the chain forward exactly once
    (``k = split(k, 3)[0]`` — per-tick and hoisted :func:`_key_block` paths
    alike), so the chain position is a pure function of (run key, ticks
    elapsed).  The sharded engine keeps the chain inside ``shard_map`` where
    it cannot be cheaply returned replicated; this recomputes it host-side
    for the resume snapshot.
    """
    if n <= 0:
        return key

    def body(k, _):
        return jax.random.split(k, 3)[0], None

    return jax.lax.scan(body, key, None, length=int(n))[0]


def _check_boundary(router: Router, t_begin: int) -> None:
    period = max(int(router.period), 1)
    dwell = max(int(router.dwell), 1)
    if t_begin % period or t_begin % dwell:
        raise ValueError(
            f"resumable chunks must start on a slow-period and dwell "
            f"boundary (t_begin % {period} == 0 and % {dwell} == 0), got "
            f"t_begin={t_begin} — pick checkpoint_every as a multiple of "
            "the router's period")


def _fresh_obs_carry(r: int, m: int, k_tiers: int):
    return (jnp.zeros((r, m), jnp.float32),
            jnp.zeros((r, k_tiers), jnp.float32),
            jnp.ones((r, k_tiers), jnp.float32),
            jnp.zeros((r, k_tiers), jnp.float32),
            jnp.ones((r, m), jnp.float32))


def resumable_rollout(router: Router,
                      carry,
                      env_state,
                      env_step: Callable,
                      n_steps: int,
                      key: jax.Array,
                      *,
                      t_begin: int = 0,
                      snapshot=None,
                      obs_masked: bool | None = None,
                      n_total: int | None = None,
                      launch_periods: int | None = None):
    """One chunk of a checkpointable rollout: ticks [t_begin, t_begin+n).

    The chunked twin of :func:`rollout` (per-tick and ``mega`` paths).  A
    fresh run is chunk 0 (``t_begin=0, snapshot=None``); every later chunk
    passes the previous chunk's returned ``snapshot`` — the opaque
    telemetry + PRNG-chain carry that, together with the router carry and
    env state, makes *stop at a boundary + resume* replay the uninterrupted
    program's op sequence exactly (bit-identical final states; pinned by
    ``tests/test_chaos.py``).  ``key`` is the *run* key: it seeds chunk 0
    and is ignored once a snapshot carries the advanced chain key.

    Chunks must start on a slow-period (and dwell) boundary so the fleet
    clock phase is statically zero.  For ``mega`` routers ``n_total`` (the
    whole horizon) must be passed on chunk 0 so the replay slots are sized
    once for the full run; ``carry`` is the previous chunk's
    :class:`~repro.core.mega.MegaFleetState` (or the fresh dense carry on
    chunk 0, kept only for the freshness validation).

    Returns (router carry, env state, trace-of-this-chunk, snapshot).
    """
    _check_boundary(router, t_begin)
    if (t_begin == 0) != (snapshot is None):
        raise ValueError(
            "chunk 0 (t_begin=0) takes snapshot=None; resumed chunks "
            "(t_begin>0) need the previous chunk's snapshot")
    if obs_masked is None:
        obs_masked = bool(getattr(env_step, "emits_mask", False))
    if getattr(router, "mega", False):
        if snapshot is None:
            obs_c = None
            state_in = None
        else:
            obs_c, key = snapshot
            state_in = carry
        state, est, trace, (obs_out, k_out) = _mega_rollout(
            router, carry if snapshot is None else None, env_state, env_step,
            n_steps, key, obs_masked=obs_masked, t0=None, t_begin=t_begin,
            state_in=state_in, obs_carry=obs_c, n_total=n_total,
            launch_periods=launch_periods)
        return state, est, trace, (obs_out, k_out)
    if launch_periods is not None:
        raise ValueError(
            "launch_periods only applies to mega routers (the per-tick "
            "engine is a single scan already); set mega=True or drop it")
    r = jax.tree_util.tree_leaves(env_state)[0].shape[0]
    if snapshot is None:
        # materialized host-side (not the in-core None default) so every
        # chunk shares one compiled program
        obs_init = _fresh_obs_carry(r, router.n_modalities, router.n_tiers)
    else:
        obs_init = snapshot[:5]
        key = snapshot[5]
    rc, est, trace, snap = _resumable_impl(
        carry, env_state, obs_init, jnp.asarray(t_begin, jnp.int32),
        env_step, n_steps, key, router=router, obs_masked=obs_masked,
        clock_phase=0)
    return rc, est, trace, snap


def sharded_resumable_rollout(router: Router,
                              carry,
                              env_state,
                              env_step: Callable,
                              n_steps: int,
                              key: jax.Array,
                              *,
                              shard,
                              n_cells: int,
                              reducer,
                              t_begin: int = 0,
                              snapshot=None,
                              obs_masked: bool | None = None):
    """One chunk of a checkpointable :func:`sharded_rollout`.

    Same contract as :func:`resumable_rollout`, on the shard_map engine:
    the snapshot is ``(obs_carry, raw_stats, chain_key)`` with the
    telemetry carry and the reducer's *unreduced* per-shard accumulator
    gathered along the (padded) cell axis, and the chain key recomputed
    host-side (:func:`_advance_chain_key`).  ``carry`` is the gathered
    router carry (chunk 0 ignores it — each shard inits its own rows).
    The returned stats are still raw; call :func:`sharded_finalize` on the
    last chunk's stats to get the psum-reduced metrics of
    :func:`sharded_rollout`.

    Returns (router carry, env state, raw stats, snapshot).
    """
    if not getattr(env_step, "supports_shard", False):
        raise ValueError(
            "env_step does not advertise supports_shard=True — sharded "
            "rollouts need a row_block-aware adapter (see "
            "repro.envsim.batched.make_env_step)")
    if getattr(router, "mega", False):
        raise ValueError("sharded_resumable_rollout does not support "
                         "mega=True (see sharded_rollout)")
    _check_boundary(router, t_begin)
    if (t_begin == 0) != (snapshot is None):
        raise ValueError(
            "chunk 0 (t_begin=0) takes snapshot=None; resumed chunks "
            "(t_begin>0) need the previous chunk's snapshot")
    r_pad, _ = shard.padded(n_cells)
    lead = jax.tree_util.tree_leaves(env_state)[0].shape[0]
    if lead != r_pad:
        raise ValueError(
            f"env_state leading dim {lead} != padded fleet size {r_pad}")
    if obs_masked is None:
        obs_masked = bool(getattr(env_step, "emits_mask", False))
    if snapshot is None:
        carry_in, obs_in, stats_in = (), (), ()
        chain_key = key
    else:
        obs_in, stats_in, chain_key = snapshot
        carry_in = carry
    rc, est, obs_out, stats_out = _sharded_chunk_impl(
        env_state, chain_key, carry_in, obs_in, stats_in,
        jnp.asarray(t_begin, jnp.int32), router=router, env_step=env_step,
        n_steps=n_steps, obs_masked=obs_masked, spec=shard, n_cells=n_cells,
        reducer=reducer, fresh=snapshot is None)
    k_next = _advance_chain_key(chain_key, n_steps)
    return rc, est, stats_out, (obs_out, stats_out, k_next)


def sharded_finalize(stats, *, shard, reducer):
    """psum-reduce a chunked run's raw stats (see sharded_resumable_rollout).

    Bit-equal to the reduction :func:`sharded_rollout` applies in-shard at
    the end of an uninterrupted run.
    """
    return _sharded_finalize_impl(stats, spec=shard, reducer=reducer)


@functools.partial(jax.jit, static_argnames=("spec", "reducer"))
def _sharded_finalize_impl(stats, *, spec, reducer):
    mesh = spec.build_mesh()
    axis = spec.axis

    def body(s):
        local = jax.tree_util.tree_map(lambda a: a[0], s)
        return reducer.finalize(local, axis)

    return jax.shard_map(body, mesh=mesh, in_specs=(P(axis),),
                         out_specs=P())(stats)


@functools.partial(jax.jit,
                   static_argnames=("router", "env_step", "n_steps",
                                    "obs_masked", "spec", "n_cells",
                                    "reducer", "fresh"),
                   donate_argnames=("env_state", "carry_in", "obs_in",
                                    "stats_in"))
def _sharded_chunk_impl(env_state,
                        key: jax.Array,
                        carry_in,
                        obs_in,
                        stats_in,
                        t_begin,
                        *,
                        router: Router,
                        env_step: Callable,
                        n_steps: int,
                        obs_masked: bool,
                        spec,
                        n_cells: int,
                        reducer,
                        fresh: bool):
    """Chunked twin of :func:`_sharded_impl`.

    ``fresh`` statically selects chunk 0 (in-shard carry/stats init, fresh
    telemetry; the snapshot pytrees arrive as empty placeholders) vs a
    resumed chunk.  Stats cross the shard_map boundary with a leading
    per-shard axis (``a[None]`` out / ``a[0]`` back in) so reducer leaves
    that lack a cell axis still gather under ``P(axis)``.
    """
    mesh = spec.build_mesh()
    r_pad, r_local = spec.padded(n_cells)
    axis = spec.axis

    def body(est, k, tb, carry_in, obs_in, stats_in):
        row0 = jax.lax.axis_index(axis) * r_local
        env_kw = ({"shard_axis": axis}
                  if getattr(env_step, "has_graph", False) else {})

        def env_local(s, w, t, kk):
            return env_step(s, w, t, kk, row_block=(row0, n_cells, r_pad),
                            **env_kw)

        if fresh:
            carry0 = router.init_carry(r_local)
            stats0 = reducer.init(r_local, row0)
            obs_init = _fresh_obs_carry(r_local, router.n_modalities,
                                        router.n_tiers)
        else:
            carry0 = carry_in
            stats0 = jax.tree_util.tree_map(lambda a: a[0], stats_in)
            obs_init = obs_in
        carry, _ = _rollout_core(
            carry0, est, env_local, n_steps, k, router=router,
            obs_masked=obs_masked, clock_phase=0,
            rows=(row0, n_cells, r_pad), reducer=reducer, stats0=stats0,
            t_begin=tb, obs_init=obs_init)
        obs_out = (carry[2], carry[3], carry[4], carry[5], carry[6])
        stats_out = jax.tree_util.tree_map(lambda a: a[None], carry[-1])
        return carry[0], carry[1], obs_out, stats_out

    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(axis), P(), P(), P(axis), P(axis), P(axis)),
        out_specs=(P(axis), P(axis), P(axis), P(axis)))(
            env_state, key, t_begin, carry_in, obs_in, stats_in)
