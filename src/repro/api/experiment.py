"""Declarative experiments: (topology, scenario, router, size, seed) -> run.

One :class:`Experiment` names everything a fleet-scale comparison needs —
the topology preset, the scenario, the fleet size / horizon / seed, the
router spec and the execution options — and :func:`run` owns all the config
assembly the examples and benchmarks used to duplicate by hand (sim config
from the topology, scenario schedules, fluid params, env adapter, router
carry, engine rollout, summary metrics).  :func:`compare` runs a list of
experiments and renders the paper's Table-1-style comparison as markdown /
JSON — on the batched engine, so "AIF vs the baseline zoo across clean and
degraded telemetry at fleet scale" is one call instead of an afternoon of
event-sim runs.

    from repro import api
    print(api.compare(api.table1_grid(n_cells=32, n_windows=600)).markdown())

Mega-fleets: set ``shard="auto"`` (or a :class:`~repro.api.shard.ShardSpec`)
and the same experiment runs device-sharded over the cell axis with
O(R/devices) trace memory — ``Experiment(router="least_loaded",
n_cells=1_000_000, shard="auto").run()`` is the one-liner.  Reduced metrics
(success %, P50/P95 via fleet-global latency histograms, tier shares,
obs fraction) replace the per-tick trace; the final env state still comes
back per-cell.
"""
from __future__ import annotations

import dataclasses
import functools
import json
import time
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.api import engine as engine_mod
from repro.api import router as router_mod
from repro.api.aif import AifRouter
from repro.api.engine import (resumable_rollout, rollout, sharded_finalize,
                              sharded_resumable_rollout, sharded_rollout)
from repro.api.shard import ShardSpec, resolve as resolve_shard
from repro.checkpoint import Checkpointer
from repro.core import generative
from repro.core import graph as graph_mod
from repro.core import mega as mega_mod
from repro.core.topology import Topology, default_topology, get_topology
from repro.envsim import batched, scenarios
from repro.envsim import chaos as chaos_mod
from repro.envsim.config import SimConfig, discretization_for, sim_config_for

_EPS = 1e-9


# ------------------------------------------------------------ router registry
def _make_aif(topo: Topology, scfg: SimConfig, use_pallas: bool, mega: bool,
              mega_slot_dtype: str = "float32",
              graph: graph_mod.FleetGraph | None = None) -> AifRouter:
    disc = discretization_for(scfg)
    if graph is not None:
        # graphed worlds emit a 5th telemetry column (neighbor pressure);
        # grow the topology's modality set and the discretization to match
        topo = graph_mod.with_neighbor_modality(topo)
        disc = dataclasses.replace(
            disc, edges=disc.modality_edges() + (graph_mod.NEIGHBOR_EDGES,))
    return AifRouter(cfg=generative.AifConfig(topology=topo),
                     disc=disc, use_pallas=use_pallas, mega=mega,
                     mega_slot_dtype=mega_slot_dtype)


def _capacity_weights(scfg: SimConfig) -> tuple[float, ...]:
    """Weights ∝ CPU limits, two-decimal rounding with the remainder on the
    heaviest tier — the paper's (0.15, 0.23, 0.62) for the 2:3:8 testbed,
    matching :class:`repro.baselines.CapacityRouter`'s default exactly so
    the ``capacity`` row is the same policy on both engines."""
    total = sum(t.servers for t in scfg.tiers)
    w = [round(t.servers / total, 2) for t in scfg.tiers[:-1]]
    return tuple(w) + (round(1.0 - sum(w), 2),)


#: Router registry: name -> (topology, sim config, use_pallas, mega, ...)
#: -> Router.  The baseline builders ignore the trailing AIF execution
#: options (``*_``) so the registry call shape can grow without touching
#: them.  ``capacity`` derives its weights from the sim config's tier CPU
#: limits — the prior knowledge AIF learns online.
ROUTERS: dict[str, Callable[..., router_mod.Router]] = {
    "aif": _make_aif,
    "uniform": lambda topo, scfg, *_:
        router_mod.UniformRouter(tiers=topo.n_tiers),
    "capacity": lambda topo, scfg, *_:
        router_mod.CapacityRouter(weights=_capacity_weights(scfg)),
    "round_robin": lambda topo, scfg, *_:
        router_mod.RoundRobinRouter(tiers=topo.n_tiers),
    "least_loaded": lambda topo, scfg, *_:
        router_mod.LeastLoadedRouter(tiers=topo.n_tiers),
    "thompson": lambda topo, scfg, *_:
        router_mod.ThompsonRouter(topology=topo),
    "ucb": lambda topo, scfg, *_:
        router_mod.UcbRouter(topology=topo),
    # OpenCDA-style nearest-neighbor offloader: greedy min estimated
    # response time (queue/capacity + service) over the live tiers — the
    # graph-aware heuristic Table 1 compares AIF against.
    "nn_offload": lambda topo, scfg, *_:
        router_mod.MinResponseRouter(
            service_s=tuple(t.mean_service_s for t in scfg.tiers),
            cap_rps=tuple(t.servers / t.mean_service_s
                          for t in scfg.tiers)),
}

#: The paper's Table-1 lineup: AIF plus the five baseline families
#: (Thompson and UCB are the two members of the bandit family), plus the
#: nearest-neighbor min-response-time offloader for the networked grids.
TABLE1_ROUTERS = ("aif", "uniform", "capacity", "round_robin",
                  "least_loaded", "thompson", "ucb", "nn_offload")


def _graphify_router(r: router_mod.Router,
                     graph: graph_mod.FleetGraph | None) -> router_mod.Router:
    """Grow a router to the graphed engine's 5-column observation.

    Baselines carry an ``extra_modalities`` pass-through field — the extra
    neighbor-pressure column rides the obs/mask plumbing unread.  Routers
    without the field (an :class:`AifRouter` instance) must already consume
    the neighbor modality; a mismatch raises here instead of surfacing as a
    scan shape error deep in the engine.
    """
    if graph is None:
        return r
    if getattr(r, "extra_modalities", None) == 0:
        r = dataclasses.replace(r, extra_modalities=1)
    expect = batched.N_OBS_MODALITIES + 1
    if r.n_modalities != expect:
        raise ValueError(
            f"graphed worlds emit {expect} observation modalities (neighbor "
            f"pressure appended) but router {r.name!r} consumes "
            f"{r.n_modalities}; build AIF via router='aif' or with "
            f"repro.core.graph.with_neighbor_modality(topology)")
    return r


# ---------------------------------------------------------- sharded reduction
#: Fleet-global latency histogram: log-spaced bins over 0.1 ms .. 1000 s.
#: 512 bins over 7 decades is ~3.2 % bin width (±1.6 % quantization on a
#: reported quantile) — below the run-to-run noise of every Table-1 metric.
_HIST_BINS = 512
_HIST_LO_S = 1e-4
_HIST_HI_S = 1e3
_HIST_SCALE = _HIST_BINS / (np.log(_HIST_HI_S) - np.log(_HIST_LO_S))


def _hist_quantile(hist: np.ndarray, q: float) -> float:
    """Mass-weighted quantile (seconds) from a log-spaced latency histogram.

    Reports the geometric midpoint of the first bin whose cumulative mass
    reaches ``q`` — the same completion-weighted convention as
    :func:`repro.envsim.batched.summarize`, quantized to the bin width.
    """
    total = hist.sum()
    if total <= 0:
        return 0.0
    idx = int(np.searchsorted(np.cumsum(hist) / total, q).clip(
        0, _HIST_BINS - 1))
    log_lo = np.log(_HIST_LO_S)
    return float(np.exp(log_lo + (idx + 0.5) / _HIST_SCALE))


@dataclasses.dataclass(frozen=True)
class FleetMetricsReducer:
    """O(cells)-memory per-tick metrics accumulator for the sharded engine.

    Replaces the stacked (T, R, ...) :class:`~repro.core.fleet.FleetTrace`
    with four small arrays folded into the scan carry — the contract
    :func:`repro.api.engine.sharded_rollout` expects (``init`` / ``update``
    / ``finalize``).  Hashable (frozen, ints only) so the engine can treat
    it as a static jit argument.

    Stats tuple: ``(valid, hist50, hist95, obs_sum, spill_sum)`` where
    ``valid`` masks this shard's phantom pad rows (cells >= the true R
    contribute zero mass to every reduction), the histograms accumulate
    completion mass over mean / P95 tier-latency atoms, ``obs_sum`` totals
    the per-cell effective-observation fraction over the steady ticks
    (t >= 1) and ``spill_sum`` totals graph-spillover mass admitted at
    neighbor cells (stays zero on ungraphed worlds).
    """

    n_cells: int

    def init(self, r_local: int, row0):
        valid = ((row0 + jnp.arange(r_local)) < self.n_cells)
        return (valid.astype(jnp.float32),
                jnp.zeros((_HIST_BINS,), jnp.float32),
                jnp.zeros((_HIST_BINS,), jnp.float32),
                jnp.zeros((), jnp.float32),
                jnp.zeros((), jnp.float32))

    @staticmethod
    def _deposit(hist, lat, mass):
        # log-spaced bin index; lat == 0 maps to -inf, clipped (as a float,
        # before the int cast) into bin 0 where its zero mass is harmless.
        idx = jnp.clip(jnp.floor((jnp.log(jnp.maximum(lat, 0.0))
                                  - np.log(_HIST_LO_S)) * _HIST_SCALE),
                       0, _HIST_BINS - 1).astype(jnp.int32)
        return hist.at[idx.ravel()].add(mass.ravel())

    def update(self, stats, t_idx, ys):
        valid, hist50, hist95, obs_sum, spill_sum = stats
        mass = ys.env.tier_completed * valid[:, None]
        hist50 = self._deposit(hist50, ys.env.tier_latency_s, mass)
        hist95 = self._deposit(hist95, ys.env.tier_p95_s, mass)
        # obs_frac[0] is the all-valid warm-up mask; count steady ticks only
        obs_sum = obs_sum + jnp.where(
            t_idx >= 1, jnp.sum(ys.obs_frac * valid), 0.0)
        spill = getattr(ys.env, "spill_admitted", None)
        if spill is not None:
            spill_sum = spill_sum + jnp.sum(spill * valid)
        return (valid, hist50, hist95, obs_sum, spill_sum)

    def update_window(self, stats, t0, ys):
        """Fold one fused window's stacked (W, ...) trace in at once.

        The whole-window mega engine produces each window's trace as one
        stacked pytree, so the reducer consumes it in one vectorized
        deposit instead of W scan iterations.  Mathematically identical to
        W sequential :meth:`update` calls (the histograms are pure
        scatter-adds; only the accumulation order differs by ulps).
        ``t0`` is the traced global tick of the window's first tick.
        """
        valid, hist50, hist95, obs_sum, spill_sum = stats
        mass = ys.env.tier_completed * valid[None, :, None]
        hist50 = self._deposit(hist50, ys.env.tier_latency_s, mass)
        hist95 = self._deposit(hist95, ys.env.tier_p95_s, mass)
        w = ys.obs_frac.shape[0]
        steady = (t0 + jnp.arange(w) >= 1).astype(jnp.float32)
        obs_sum = obs_sum + jnp.sum(
            steady[:, None] * ys.obs_frac * valid[None, :])
        spill = getattr(ys.env, "spill_admitted", None)
        if spill is not None:
            spill_sum = spill_sum + jnp.sum(spill * valid[None, :])
        return (valid, hist50, hist95, obs_sum, spill_sum)

    def finalize(self, stats, axis: str):
        _, hist50, hist95, obs_sum, spill_sum = stats
        return (jax.lax.psum(hist50, axis), jax.lax.psum(hist95, axis),
                jax.lax.psum(obs_sum, axis), jax.lax.psum(spill_sum, axis))


@dataclasses.dataclass(frozen=True)
class Experiment:
    """One declarative fleet experiment (hashable, JSON-friendly).

    Args:
      router: registry name (:data:`ROUTERS`) or a ready
        :class:`~repro.api.router.Router` instance.
      scenario: scenario preset (:data:`repro.envsim.scenarios.SCENARIOS`).
      topology: topology preset name (:data:`repro.core.topology.TOPOLOGIES`)
        or a :class:`~repro.core.topology.Topology`.
      n_cells / n_windows: fleet size R and horizon T.
      seed: drives the scenario schedules and the rollout PRNG.
      window_s: control-window length in seconds.
      use_pallas: with ``mega``, run each window as the Pallas megakernel
        instead of its XLA oracle (ignored for baselines; raises for AIF
        without ``mega``).
      mega: run AIF on the whole-window megakernel engine path (the
        multi-period super-launch: one jit spans the run, factored
        transition cache, streaming slow boundaries — see
        :mod:`repro.core.mega`).  Requires a fresh fleet clock, so the run
        always starts from ``carry=None``.  Composes with ``shard``: the
        super-launch then runs per device shard with on-device metric
        reduction (bit-identical to unsharded on a 1-device mesh).
      mega_slot_dtype: storage dtype of the megakernel's transition slots
        ("float32" or "bfloat16" — mixed precision: bf16 store, fp32
        accumulate).
      launch_periods: mega only — dispatch the super-launch in chunks of
        this many slow periods instead of one jit over the whole horizon
        (actions and final state bit-identical, telemetry floats within
        ulps; bounds compile scope).  None = single launch.  Not available
        with ``shard`` (the sharded super-launch is one program).
      shard: device sharding of the cell axis — None (unsharded engine,
        full per-tick trace), ``"auto"`` (all local devices) or a
        :class:`~repro.api.shard.ShardSpec`.  Sharded runs keep trace
        memory at O(R/devices) by reducing metrics on device; R is padded
        up to a device multiple with inert phantom cells unless the spec
        says ``pad="strict"``.  Results are invariant to the device count.
      checkpoint_every: windows between checkpoints (0 = off).  Must be a
        multiple of the router's slow period (and dwell) so every chunk
        boundary sits on a fleet-clock phase of zero; the run then executes
        as boundary-aligned :func:`~repro.api.engine.resumable_rollout`
        chunks whose concatenation is bit-identical to the uninterrupted
        program, and a :class:`~repro.checkpoint.Checkpointer` snapshot
        (router carry + env state + telemetry/PRNG snapshot) lands at every
        interior boundary.
      checkpoint_dir: where the checkpoints go (required when
        ``checkpoint_every > 0``; defaults to ``resume_from``).
      resume_from: checkpoint directory of a previous (interrupted) run of
        this same experiment — the run restores the newest readable
        checkpoint (corrupt ones are skipped with a warning) and continues
        to ``n_windows``.  The final states are bit-identical to the
        uninterrupted run; trace-derived metrics cover the post-resume
        windows only (the cumulative env counters still cover the whole
        horizon).  Sharded resumes need the same device count the
        checkpoint was written under.
      label: display name (default: the router name).
      graph: networked-continuum fleet graph — None (ungraphed; the three
        graph scenario presets auto-attach their matching
        :data:`repro.core.graph.GRAPH_PRESETS` entry), a preset name
        (``"ring"`` / ``"grid"`` / ``"hier"`` / ``"none"`` — the last
        forces the ungraphed program even on a graph scenario, the
        acceptance control), or a ready
        :class:`~repro.core.graph.FleetGraph`.  A graphed world spills
        rejected load to graph neighbors (hop-latency penalty) and emits
        a 5th neighbor-pressure telemetry modality; registry routers grow
        to consume it automatically.
    """

    router: str | router_mod.Router = "aif"
    scenario: str = "paper-burst"
    topology: str | Topology = "paper-3tier"
    n_cells: int = 8
    n_windows: int = 300
    seed: int = 0
    window_s: float = 1.0
    use_pallas: bool = False
    mega: bool = False
    mega_slot_dtype: str = "float32"
    launch_periods: int | None = None
    shard: ShardSpec | str | None = None
    checkpoint_every: int = 0
    checkpoint_dir: str | None = None
    resume_from: str | None = None
    label: str | None = None
    graph: graph_mod.FleetGraph | str | None = None

    def resolve_topology(self) -> Topology:
        return (get_topology(self.topology)
                if isinstance(self.topology, str) else self.topology)

    def resolve_graph(self) -> graph_mod.FleetGraph | None:
        """The effective fleet graph (None = the exact ungraphed program).

        Resolution order: an explicit :class:`FleetGraph` / preset name
        wins; otherwise the graph scenario presets auto-attach their
        matching graph; ``graph="none"`` always resolves to None.
        """
        return graph_mod.resolve_graph(self.graph, self.n_cells,
                                       scenario=self.scenario)

    def resolve_router(self, scfg: SimConfig,
                       graph: graph_mod.FleetGraph | None = None
                       ) -> router_mod.Router:
        if isinstance(self.router, router_mod.Router):
            if self.use_pallas or self.mega:
                raise ValueError(
                    "use_pallas/mega only apply to registry-built routers; "
                    "set them on the Router instance itself (e.g. "
                    "AifRouter(mega=True)) — silently ignoring them would "
                    "misreport which execution path ran")
            return _graphify_router(self.router, graph)
        try:
            make = ROUTERS[self.router]
        except KeyError:
            raise KeyError(f"unknown router {self.router!r}; "
                           f"available: {sorted(ROUTERS)}") from None
        if self.router == "aif":
            return _make_aif(self.resolve_topology(), scfg,
                             self.use_pallas, self.mega,
                             self.mega_slot_dtype, graph=graph)
        return _graphify_router(
            make(self.resolve_topology(), scfg, self.use_pallas,
                 self.mega), graph)

    @property
    def name(self) -> str:
        if self.label:
            return self.label
        return (self.router if isinstance(self.router, str)
                else self.router.name)


@dataclasses.dataclass
class RunResult:
    """Standardized outcome of one experiment (Table-1 row + raw artifacts).

    Scalar metrics aggregate over the R cells; the per-cell
    :class:`~repro.envsim.batched.FluidResult`, the
    :class:`~repro.core.fleet.FleetTrace` and the final router carry stay
    attached for drill-down (belief health checks, weight trajectories).

    ``success_pct`` is the mean of per-cell success rates on ungraphed
    worlds and the *fleet-global* ratio ΣnSuccess/ΣnRequests on graphed
    ones (spillover credits completions at the receiving cell, so per-cell
    ratios are not meaningful there); compare graphed vs ungraphed runs on
    ``fluid.n_success.sum() / fluid.n_requests.sum()``.
    """

    experiment: Experiment
    name: str
    success_pct: float            # mean over cells, percent
    success_std: float            # std over cells, percent
    p50_ms: float
    p95_ms: float
    tier_share: np.ndarray        # (K,) share of successes, lightest first
    routed_share: np.ndarray      # (K,) share of routed requests
    restarts: float               # pod restarts summed over fleet
    obs_frac: float               # effective-observation fraction
    wall_s: float                 # rollout: state set-up, launches, wait
    fluid: batched.FluidResult
    trace: Any                    # None on sharded runs (metrics reduced)
    final_carry: Any
    cells_per_device: int = 0     # R/devices after padding (R if unsharded)
    watchdog_events: float = 0.0  # quarantine-and-reinit events over the run
    resume_points: tuple = ()     # chunk boundaries (windows): interior
    #                               checkpoint saves, plus the restored
    #                               start window on a resumed run
    recovery: dict | None = None  # chaos recovery metrics (None: scenario
    #                               has no registered control, or sharded
    #                               run — no per-window trace to curve over)
    offload_frac: float = 0.0     # fraction of offered load absorbed at a
    #                               graph neighbor after spillover (0.0 on
    #                               ungraphed worlds)

    def summary(self) -> dict:
        """JSON-safe metric dict (one Table-1 row)."""
        return {
            "router": self.name,
            "scenario": self.experiment.scenario,
            "n_cells": self.experiment.n_cells,
            "n_windows": self.experiment.n_windows,
            "success_pct": round(self.success_pct, 2),
            "success_std": round(self.success_std, 2),
            "p50_ms": round(self.p50_ms, 1),
            "p95_ms": round(self.p95_ms, 1),
            "tier_share_of_success": [round(float(x), 4)
                                      for x in self.tier_share],
            "routed_share": [round(float(x), 4) for x in self.routed_share],
            "restarts": round(self.restarts, 1),
            "obs_frac": round(self.obs_frac, 4),
            "offload_frac": round(self.offload_frac, 4),
            "wall_s": round(self.wall_s, 2),
            "cells_per_device": self.cells_per_device,
            "watchdog_events": round(self.watchdog_events, 1),
            **({"recovery": {k: (round(v, 4) if isinstance(v, float) else v)
                             for k, v in self.recovery.items()}}
               if self.recovery is not None else {}),
        }


@functools.lru_cache(maxsize=8)
def _build_world(topo: Topology, scenario: str, n_cells: int, n_windows: int,
                 window_s: float, seed: int,
                 graph: graph_mod.FleetGraph | None = None):
    """(sim config, fluid params, env_step) for one experiment's world.

    Deterministic in its arguments, and cached so repeated runs of the same
    experiment reuse the *same* ``env_step`` closure — the engine hashes it
    as a static jit argument by identity, so this is what turns a re-run
    into a jit cache hit instead of a recompile.
    """
    # The paper's testbed keeps its calibrated 50 RPS config; other
    # topologies get the just-under-saturation config derived from their
    # capacity classes.
    scfg = (SimConfig() if topo == default_topology()
            else sim_config_for(topo))
    sc = scenarios.build_scenario(scenario, scfg, n_cells, n_windows,
                                  window_s=window_s, seed=seed)
    params = batched.params_from_config(scfg, n_cells, sc.capacity_scale)
    env_step = batched.make_scenario_env_step(params, sc, dt=window_s,
                                              graph=graph)
    return scfg, params, env_step


@functools.lru_cache(maxsize=8)
def _build_world_padded(topo: Topology, scenario: str, n_cells: int,
                        n_windows: int, window_s: float, seed: int,
                        r_pad: int, n_devices: int,
                        graph: graph_mod.FleetGraph | None = None):
    """Sharded variant of :func:`_build_world`: true-R world, padded to the
    device multiple.

    The scenario is *built* at the true R (its per-cell randomness is a
    function of R — building at ``r_pad`` would change every real cell's
    schedule with the device count) and then padded with inert phantom
    cells (:func:`repro.envsim.scenarios.pad_scenario`); the fluid params
    and env adapter live at ``r_pad``.  The cache key carries both the
    padded size and the resolved device count — two shard specs that pad
    the same R differently (or the same spec under a different
    ``XLA_FLAGS`` device count) must not share an ``env_step`` closure,
    or the engine's identity-hashed static jit arg would replay a stale
    world shape.
    """
    scfg = (SimConfig() if topo == default_topology()
            else sim_config_for(topo))
    sc = scenarios.build_scenario(scenario, scfg, n_cells, n_windows,
                                  window_s=window_s, seed=seed)
    sc = scenarios.pad_scenario(sc, r_pad)
    params = batched.params_from_config(scfg, r_pad, sc.capacity_scale)
    if graph is not None:
        # phantom pad rows must stay edge-less and inert (see pad_scenario)
        graph.validate_true_rows(n_cells)
    env_step = batched.make_scenario_env_step(params, sc, dt=window_s,
                                              graph=graph)
    return scfg, params, env_step


def run(experiment: Experiment) -> RunResult:
    """Assemble and execute one experiment on the batched engine.

    Builds the sim config from the topology preset, materializes the
    scenario schedules, adapts the fluid engine, initializes the router
    carry and runs the whole closed loop as one jitted scan — the plumbing
    previously copy-pasted across every example and benchmark.

    Chaos scenarios (:data:`repro.envsim.chaos.CHAOS_INFO`) additionally
    get recovery metrics: the same experiment is re-run on the registered
    uninjured *control* scenario and the per-window success curves are
    compared (``RunResult.recovery``) — sharded runs skip this (their trace
    is reduced away on device).

    Under a profiler trace the call is one ``repro.run`` span (args
    ``run``, ``n_cells``, ``n_windows``, ``path``) whose children name each
    host step; :mod:`repro.obs` lists them and counts runs, launches,
    cell-windows, watchdog events and compiles.
    """
    e = experiment
    with obs.run_span(n_cells=e.n_cells, n_windows=e.n_windows) as sp:
        with obs.span("run.world"):
            topo = e.resolve_topology()
            spec = resolve_shard(e.shard)
            params, env_step, router = _world(e, topo, spec,
                                              e.resolve_graph())
        sp.set_metadata(path=_path(router, spec))
        res = (_run_sharded(e, spec, router, params, env_step)
               if spec is not None
               else _run_dense(e, router, params, env_step))
        info = chaos_mod.CHAOS_INFO.get(e.scenario)
        if info is not None and res.trace is not None:
            control = run(dataclasses.replace(
                e, scenario=info.base, checkpoint_every=0,
                checkpoint_dir=None, resume_from=None))
            res.recovery = _recovery_metrics(e, info, res, control)
        obs.count("cell_windows", e.n_cells * e.n_windows)
        obs.count("watchdog_events", round(res.watchdog_events))
    return res


def _world(e: Experiment, topo: Topology, spec: ShardSpec | None,
           graph: graph_mod.FleetGraph | None):
    """(fluid params, env_step, router) of one experiment: the memoized
    world (padded to the device multiple on sharded runs) and the router
    resolved against it."""
    if spec is None:
        scfg, params, env_step = _build_world(
            topo, e.scenario, e.n_cells, e.n_windows, e.window_s, e.seed,
            graph)
    else:
        if e.launch_periods is not None:
            raise ValueError(
                "launch_periods is not available on sharded runs — the "
                "sharded super-launch is a single shard_map program; drop "
                "shard or launch_periods")
        scfg, params, env_step = _build_world_padded(
            topo, e.scenario, e.n_cells, e.n_windows, e.window_s, e.seed,
            spec.padded(e.n_cells)[0], spec.n_devices(), graph)
    router = e.resolve_router(scfg, graph)
    if router.n_tiers != topo.n_tiers:
        raise ValueError(
            f"router {router.name!r} routes over {router.n_tiers} tiers but "
            f"topology {topo.tier_names} has {topo.n_tiers}")
    return params, env_step, router


def _path(router, spec: ShardSpec | None) -> str:
    """The engine path a run takes, as its ``repro.run`` span names it."""
    if spec is not None:
        return "sharded"
    if not getattr(router, "mega", False):
        return "tick"
    return "mega.pallas" if router.use_pallas else "mega.xla"


def _fetch(leaves) -> None:
    """Copy device arrays to the host in one ``repro.run.summarize.fetch``
    span; each array keeps its host copy, so the reduction that follows
    reads them without another transfer."""
    with obs.span("run.summarize.fetch",
                  bytes=sum(int(x.nbytes) for x in leaves)):
        for x in leaves:
            np.asarray(x)


def _run_dense(e: Experiment, router, params, env_step) -> RunResult:
    """Unsharded execution path of :func:`run` (per-tick or mega engine)."""
    n_mod = getattr(env_step, "n_obs_modalities", batched.N_OBS_MODALITIES)

    t0 = time.perf_counter()
    if e.checkpoint_every or e.resume_from:
        carry, est, trace, boundaries = _chunked_rollout(e, router, params,
                                                         env_step)
    else:
        with obs.span("run.init"):
            # mega routers own their carry (factored MegaFleetState, fresh
            # clock)
            init = (None if getattr(router, "mega", False)
                    else router.init_carry(e.n_cells))
            est0 = batched.init_fluid_state(params, n_modalities=n_mod)
            key = jax.random.key(e.seed)
        carry, est, trace = rollout(router, init, est0, env_step,
                                    e.n_windows, key,
                                    launch_periods=e.launch_periods)
        boundaries = ()
    with obs.span("run.wait"):
        jax.block_until_ready(est)
    wall = time.perf_counter() - t0

    with obs.span("run.summarize"):
        spill = getattr(trace.env, "spill_admitted", None)
        # the (T, R, K) traces the per-cell reduction transposes, copied
        # one by one in its order as it would copy them itself; the rest is
        # small or copied where it is summed
        _fetch([trace.env.tier_p95_s, trace.env.tier_latency_s,
                trace.env.tier_completed])
        with obs.span("run.summarize.reduce"):
            res = batched.summarize(est, trace.env)
            succ = 100.0 * res.success_rate
            # spillover credits completions at the receiving cell while the
            # request was counted at its origin, so per-cell ratios can
            # exceed 1 on graphed worlds; report the fleet-global ratio
            # there (identical semantics fleet-wide, and conservation
            # bounds it by 100).
            succ_mean = (100.0 * float(res.n_success.sum())
                         / max(float(res.n_requests.sum()), 1.0)
                         if getattr(env_step, "has_graph", False)
                         else float(succ.mean()))
            n_success = np.maximum(res.n_success, _EPS)
            n_req = np.maximum(res.n_requests, _EPS)
            tier_share = (res.tier_success / n_success[:, None]).mean(0)
            routed_share = (res.tier_requests / n_req[:, None]).mean(0)
            obs_frac = np.asarray(trace.obs_frac)
            # obs_frac[0] is the all-valid warm-up mask; report the steady
            # part
            obs_steady = (float(obs_frac[1:].mean())
                          if obs_frac.shape[0] > 1 else 1.0)
            offload = (0.0 if spill is None else
                       float(np.asarray(spill, np.float64).sum()
                             / max(float(res.n_requests.sum()), 1.0)))
            return RunResult(
                experiment=e,
                name=e.name,
                success_pct=succ_mean,
                success_std=float(succ.std()),
                p50_ms=float(res.p50_ms.mean()),
                p95_ms=float(res.p95_ms.mean()),
                tier_share=tier_share,
                routed_share=routed_share,
                restarts=float(res.n_restarts.sum()),
                obs_frac=obs_steady,
                wall_s=wall,
                fluid=res,
                trace=trace,
                final_carry=carry,
                cells_per_device=e.n_cells,
                watchdog_events=_watchdog_total(trace),
                resume_points=tuple(boundaries),
                offload_frac=offload,
            )


# ------------------------------------------- checkpointing + recovery metrics
def _watchdog_total(trace) -> float:
    """Total quarantine-and-reinit events recorded in a trace (0.0 if the
    router has no watchdog or the trace was reduced away)."""
    wd = getattr(trace, "watchdog", None)
    return float(np.asarray(wd).sum()) if wd is not None else 0.0


def _ckpt_payload(e: Experiment, router, carry, env, snapshot, sharded: bool):
    """Checkpoint tree for one boundary: engine snapshot split into its
    telemetry / reducer-stats / PRNG-chain parts (typed keys stored as raw
    key data — ``.npy`` cannot hold extended dtypes)."""
    if sharded:
        obs, stats, chain = snapshot
        extra_stats = {"stats": stats}
    elif getattr(router, "mega", False):
        (obs, chain), extra_stats = snapshot, {}
    else:
        obs, chain, extra_stats = snapshot[:5], snapshot[5], {}
    return {"carry": carry, "env": env, "obs": tuple(obs),
            "key": jax.random.key_data(chain), **extra_stats}


def _ckpt_template(e: Experiment, router, params, spec: ShardSpec | None,
                   reducer=None, n_modalities=batched.N_OBS_MODALITIES):
    """Shape/dtype template matching :func:`_ckpt_payload` for restore."""
    env_t = batched.init_fluid_state(params, n_modalities=n_modalities)
    r = jax.tree_util.tree_leaves(env_t)[0].shape[0]
    if getattr(router, "mega", False):
        slot_dtype = (jnp.bfloat16 if router.mega_slot_dtype == "bfloat16"
                      else jnp.float32)
        carry_t = mega_mod.init_mega_state(router.cfg, r, e.n_windows,
                                           slot_dtype=slot_dtype)
    else:
        carry_t = router.init_carry(r)
    tmpl = {"carry": carry_t, "env": env_t,
            "obs": engine_mod._fresh_obs_carry(r, router.n_modalities,
                                               router.n_tiers),
            "key": jax.random.key_data(jax.random.key(0))}
    if spec is not None:
        _, r_local = spec.padded(e.n_cells)
        stats0 = reducer.init(r_local, jnp.zeros((), jnp.int32))
        tmpl["stats"] = jax.tree_util.tree_map(
            lambda a: jnp.zeros((spec.n_devices(),) + a.shape, a.dtype),
            stats0)
    return tmpl


def _ckpt_setup(e: Experiment, router, params, spec=None, reducer=None,
                n_modalities=batched.N_OBS_MODALITIES):
    """Shared chunk-loop state: (checkpointer, resume point, restored
    pieces).  Chunk boundaries are validated once — every boundary is a
    multiple of ``checkpoint_every``, so alignment of the stride implies
    alignment of them all."""
    if e.checkpoint_every:
        engine_mod._check_boundary(router, int(e.checkpoint_every))
    ck_dir = e.checkpoint_dir or e.resume_from
    if e.checkpoint_every and not ck_dir:
        raise ValueError("checkpoint_every > 0 needs checkpoint_dir "
                         "(or resume_from) to say where snapshots go")
    ckpt = Checkpointer(ck_dir) if ck_dir else None
    if not e.resume_from:
        return ckpt, 0, None, None, None
    tree, extra = Checkpointer(e.resume_from).restore(
        _ckpt_template(e, router, params, spec, reducer, n_modalities))
    t_begin = int(extra["t"])
    if extra.get("scenario") not in (None, e.scenario):
        raise ValueError(
            f"resume_from checkpoint was written for scenario "
            f"{extra['scenario']!r}, not {e.scenario!r} — resuming would "
            f"splice two different worlds")
    if t_begin >= e.n_windows:
        raise ValueError(f"checkpoint is at window {t_begin} but the "
                         f"experiment ends at {e.n_windows}")
    chain = jax.random.wrap_key_data(tree["key"])
    obs = tuple(tree["obs"])
    if spec is not None:
        snapshot = (obs, tree["stats"], chain)
    elif getattr(router, "mega", False):
        snapshot = (obs, chain)
    else:
        snapshot = obs + (chain,)
    return ckpt, t_begin, tree["carry"], tree["env"], snapshot


def _chunk_sizes(e: Experiment, t_begin: int):
    t = t_begin
    while t < e.n_windows:
        n = (min(e.checkpoint_every, e.n_windows - t) if e.checkpoint_every
             else e.n_windows - t)
        yield t, n
        t += n


def _chunked_rollout(e: Experiment, router, params, env_step):
    """Checkpointed twin of the dense single-scan rollout.

    Runs ``resumable_rollout`` chunks between boundary-aligned windows,
    saving (router carry, env state, engine snapshot) at every interior
    boundary; the concatenated trace and final states are bit-identical to
    the uninterrupted program (``tests/test_chaos.py``).
    """
    mega = bool(getattr(router, "mega", False))
    n_mod = getattr(env_step, "n_obs_modalities", batched.N_OBS_MODALITIES)
    with obs.span("run.init"):
        ckpt, t_begin, carry, env, snapshot = _ckpt_setup(
            e, router, params, n_modalities=n_mod)
        if not e.resume_from:
            carry = None if mega else router.init_carry(e.n_cells)
            env = batched.init_fluid_state(params, n_modalities=n_mod)
        key = jax.random.key(e.seed)
    traces, boundaries = [], ([t_begin] if t_begin else [])
    for t, n in _chunk_sizes(e, t_begin):
        carry, env, tr, snapshot = resumable_rollout(
            router, carry, env, env_step, n, key, t_begin=t,
            snapshot=snapshot, n_total=(e.n_windows if mega else None),
            launch_periods=(e.launch_periods if mega else None))
        with obs.span("run.wait"):
            traces.append(jax.device_get(tr))
        if t + n < e.n_windows:
            boundaries.append(t + n)
            if ckpt is not None:
                ckpt.save(t + n,
                          _ckpt_payload(e, router, carry, env, snapshot,
                                        sharded=False),
                          extra={"t": t + n, "scenario": e.scenario,
                                 "seed": e.seed})
    if ckpt is not None:
        ckpt.wait()
    trace = jax.tree_util.tree_map(
        lambda *xs: np.concatenate([np.asarray(x) for x in xs], axis=0),
        *traces)
    return carry, env, trace, tuple(boundaries)


def _sharded_chunked(e: Experiment, router, params, env_step,
                     spec: ShardSpec, reducer):
    """Checkpointed twin of :func:`sharded_rollout` (shard_map engine).

    The snapshot additionally carries the reducer's raw per-shard stats
    (gathered with a leading device axis); :func:`sharded_finalize` reduces
    the last chunk's stats exactly as the uninterrupted run does in-shard.
    """
    n_mod = getattr(env_step, "n_obs_modalities", batched.N_OBS_MODALITIES)
    with obs.span("run.init"):
        ckpt, t_begin, carry, env, snapshot = _ckpt_setup(
            e, router, params, spec, reducer, n_modalities=n_mod)
        if not e.resume_from:
            carry, env = None, batched.init_fluid_state(params,
                                                        n_modalities=n_mod)
        key = jax.random.key(e.seed)
    boundaries, stats = ([t_begin] if t_begin else []), None
    for t, n in _chunk_sizes(e, t_begin):
        carry, env, stats, snapshot = sharded_resumable_rollout(
            router, carry, env, env_step, n, key, shard=spec,
            n_cells=e.n_cells, reducer=reducer, t_begin=t, snapshot=snapshot)
        if t + n < e.n_windows:
            boundaries.append(t + n)
            if ckpt is not None:
                ckpt.save(t + n,
                          _ckpt_payload(e, router, carry, env, snapshot,
                                        sharded=True),
                          extra={"t": t + n, "scenario": e.scenario,
                                 "seed": e.seed})
    if ckpt is not None:
        ckpt.wait()
    return carry, env, sharded_finalize(stats, shard=spec, reducer=reducer), \
        tuple(boundaries)


def _recovery_metrics(e: Experiment, info, res: RunResult,
                      control: RunResult) -> dict:
    """Recovery curve of a chaos run against its uninjured control.

    * ``time_to_recover_s`` — windows after the fault clears until the
      fleet success rate re-enters 95 % of the control's, in seconds
      (horizon remainder when it never does — finite either way, with
      ``recovered`` saying which).
    * ``regret_vs_control`` — mean per-window success-rate shortfall
      (clipped at 0) against the control over the traced windows.
    * ``post_resume_forgetting`` — mean drop in success rate across the
      run's resume boundaries (last-5-windows-before minus
      first-5-windows-after); 0 when nothing resumed.  Bit-exact resume
      makes this indistinguishable from the local trend — the metric
      exists to catch a *broken* resume path, not to measure one that
      works.
    """
    rate = _success_curve(res.trace)
    rate_c = _success_curve(control.trace)
    n = min(len(rate), len(rate_c))      # resumed runs trace a suffix only
    rate, rate_c = rate[-n:], rate_c[-n:]
    regret = float(np.maximum(rate_c - rate, 0.0).mean())

    t_end = int(np.ceil(info.fault_frac[1] * e.n_windows))
    i0 = max(t_end - (e.n_windows - n), 0)
    ok = rate[i0:] >= 0.95 * rate_c[i0:]
    recovered = bool(ok.any())
    ttr = int(np.argmax(ok)) if recovered else max(len(rate) - i0, 0)

    offset = e.n_windows - n
    w = 5
    drops = [float(rate[b - w:b].mean() - rate[b:b + w].mean())
             for b in (p - offset for p in res.resume_points)
             if b - w >= 0 and b + w <= n]
    return {
        "time_to_recover_s": float(ttr) * e.window_s,
        "recovered": recovered,
        "regret_vs_control": regret,
        "post_resume_forgetting": (float(np.mean(drops)) if drops else 0.0),
        "control_success_pct": control.success_pct,
        "watchdog_events": res.watchdog_events,
    }


def _success_curve(trace) -> np.ndarray:
    """(T,) fleet success rate per window from a dense trace."""
    s = np.asarray(trace.env.success).sum(axis=1)
    f = np.asarray(trace.env.failures).sum(axis=1)
    return s / np.maximum(s + f, _EPS)


def _run_sharded(e: Experiment, spec: ShardSpec, router, params,
                 env_step) -> RunResult:
    """Device-sharded execution path of :func:`run`.

    Same world, same router, same PRNG stream — but the rollout runs under
    ``shard_map`` with on-device metric reduction instead of a stacked
    trace, so ``RunResult.trace`` is None and P50/P95 are *fleet-global*
    completion-weighted quantiles (from the reducer's latency histograms)
    rather than the unsharded path's mean of per-cell quantiles.  The final
    env state still comes back per-cell, so success %, tier shares, error
    breakdown and restarts are computed exactly as in the unsharded path —
    on the true R rows only.
    """
    _, r_local = spec.padded(e.n_cells)
    reducer = FleetMetricsReducer(n_cells=e.n_cells)
    n_mod = getattr(env_step, "n_obs_modalities", batched.N_OBS_MODALITIES)

    t0 = time.perf_counter()
    boundaries: tuple = ()
    if e.checkpoint_every or e.resume_from:
        carry, est, stats, boundaries = _sharded_chunked(
            e, router, params, env_step, spec, reducer)
    else:
        with obs.span("run.init"):
            est0 = batched.init_fluid_state(params, n_modalities=n_mod)
            key = jax.random.key(e.seed)
        carry, est, stats = sharded_rollout(
            router, est0, env_step, e.n_windows, key, shard=spec,
            n_cells=e.n_cells, reducer=reducer)
    with obs.span("run.wait"):
        jax.block_until_ready(stats)
    wall = time.perf_counter() - t0

    with obs.span("run.summarize"):
        _fetch(jax.tree_util.tree_leaves((stats, est)))
        with obs.span("run.summarize.reduce"):
            hist50, hist95, obs_sum, spill_sum = (np.asarray(s)
                                                  for s in stats)
            p50_s = _hist_quantile(hist50, 0.50)
            p95_s = _hist_quantile(hist95, 0.95)
            # slice the phantom pad rows off the gathered final state, then
            # reuse the per-cell accounting (quantile columns get the
            # fleet-global values — per-cell quantiles would need the
            # trace the sharded path avoids)
            final = jax.tree_util.tree_map(
                lambda a: np.asarray(a)[:e.n_cells], est)
            n_req = np.maximum(final.n_requests, _EPS)
            n_success = np.maximum(final.n_success, _EPS)
            res = batched.FluidResult(
                n_requests=final.n_requests,
                n_success=final.n_success,
                success_rate=final.n_success / n_req,
                error_breakdown={
                    "timeout": final.err_timeout,
                    "overflow": final.err_overflow,
                    "refused": final.err_refused,
                    "restart": final.err_restart,
                },
                p95_ms=np.full(e.n_cells, 1000.0 * p95_s),
                p50_ms=np.full(e.n_cells, 1000.0 * p50_s),
                tier_requests=final.tier_requests,
                tier_success=final.tier_success,
                n_restarts=final.n_restarts,
            )
            succ = 100.0 * res.success_rate
            succ_mean = (100.0 * float(final.n_success.sum())
                         / max(float(final.n_requests.sum()), 1.0)
                         if getattr(env_step, "has_graph", False)
                         else float(succ.mean()))
            steady = max(e.n_windows - 1, 1) * e.n_cells
            return RunResult(
                experiment=e,
                name=e.name,
                success_pct=succ_mean,
                success_std=float(succ.std()),
                p50_ms=float(1000.0 * p50_s),
                p95_ms=float(1000.0 * p95_s),
                tier_share=(res.tier_success / n_success[:, None]).mean(0),
                routed_share=(res.tier_requests / n_req[:, None]).mean(0),
                restarts=float(res.n_restarts.sum()),
                obs_frac=(float(obs_sum) / steady if e.n_windows > 1
                          else 1.0),
                wall_s=wall,
                fluid=res,
                trace=None,
                final_carry=carry,
                cells_per_device=r_local,
                resume_points=tuple(boundaries),
                offload_frac=float(spill_sum)
                / max(float(final.n_requests.sum()), 1.0),
            )


# ------------------------------------------------------------------ comparison
@dataclasses.dataclass
class Comparison:
    """Results of a comparison grid, renderable as markdown or JSON."""

    results: list[RunResult]

    def markdown(self) -> str:
        """Table-1-style markdown: one row per (scenario, router)."""
        lines = [
            "| scenario | router | success % | P50 ms | P95 ms | "
            "tier share of success (light->heavy) | obs % | offload % |",
            "|---|---|---|---|---|---|---|---|",
        ]
        for res in self.results:
            share = "/".join(f"{100 * float(x):.0f}" for x in res.tier_share)
            lines.append(
                f"| {res.experiment.scenario} | {res.name} "
                f"| {res.success_pct:.1f} ± {res.success_std:.1f} "
                f"| {res.p50_ms:.0f} | {res.p95_ms:.0f} "
                f"| {share} | {100 * res.obs_frac:.0f} "
                f"| {100 * res.offload_frac:.1f} |")
        return "\n".join(lines)

    def to_json(self) -> dict:
        """{scenario: {router: summary}} nested metric dict.

        Rows sharing (scenario, router name) — e.g. the same router at two
        seeds — are disambiguated with a ``#2``, ``#3`` ... suffix so the
        artifact never silently drops a row the markdown table shows.
        """
        out: dict[str, dict] = {}
        for res in self.results:
            rows = out.setdefault(res.experiment.scenario, {})
            name, n = res.name, 1
            while name in rows:
                n += 1
                name = f"{res.name}#{n}"
            rows[name] = res.summary()
        return out

    def dump(self, path: str) -> None:
        with open(path, "w") as f:
            json.dump(self.to_json(), f, indent=1)

    __str__ = markdown


def compare(experiments: Sequence[Experiment]) -> Comparison:
    """Run a list of experiments and collect them into a :class:`Comparison`.

    Experiments sharing (scenario, topology, R, T, seed) run against
    identical world schedules — the registry builders are deterministic in
    the experiment seed — so rows differ only by routing policy, the paper's
    Table-1 protocol at fleet scale.
    """
    return Comparison(results=[run(e) for e in experiments])


def table1_grid(routers: Sequence[str] = TABLE1_ROUTERS,
                scenario_names: Sequence[str] = ("paper-burst",
                                                 "flaky-telemetry"),
                **overrides) -> list[Experiment]:
    """The paper's comparison grid: router zoo × clean + degraded telemetry.

    ``overrides`` forward to every :class:`Experiment` (n_cells, n_windows,
    seed, topology, mega, ...).
    """
    return [Experiment(router=r, scenario=s, **overrides)
            for s in scenario_names for r in routers]
