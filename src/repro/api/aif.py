"""The AIF agent adapted onto the :class:`repro.api.router.Router` protocol.

This is the paper's router as a fleet policy: the spec wraps everything the
old 13-argument ``fleet_rollout`` signature hand-assembled (agent config,
observation discretization, utilization-scrape edges/cadence, engine
path) into one hashable object the engine treats as a static
jit argument.  The step/light/slow hooks are *exactly* the agent-side body
of the pre-refactor ``fleet_rollout`` tick (same ops, same order, same PRNG
consumption), so the AIF path through :func:`repro.api.engine.rollout` is
bit-identical to the old entry point — the golden rollout test pins this.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import agent as agent_mod
from repro.core import fleet as fleet_mod
from repro.core import generative, spaces
from repro.api.router import Router, RouterObs, TickInfo


@dataclasses.dataclass(frozen=True)
class AifRouter(Router):
    """Fleet spec of the Active Inference router (paper §4).

    Args:
      cfg: agent hyper-parameters; ``cfg.topology`` fixes every shape.
      disc: observation discretization (None = paper defaults); its edge
        rows must match the topology's modalities.
      util_edges: raw-utilization level edges (None = the topology's).
      util_period: windows between utilization scrapes.
      mega: run the whole-window megakernel engine path — the transition
        model stays in factored (slot) form, the whole rollout fuses into
        one super-launch (periods scanned inside; chunk with the engine's
        ``launch_periods``) and the rollout carry becomes a
        :class:`repro.core.mega.MegaFleetState` (densify with
        :func:`repro.core.mega.to_agent_state`).  Without it the router
        runs the per-tick reference engine (:mod:`repro.core.fleet`).
      use_pallas: with ``mega``, each window dispatches the Pallas
        megakernel instead of its XLA oracle.  It has no meaning on the
        per-tick engine, so setting it without ``mega`` raises.
      mega_slot_dtype: storage dtype of the (R, J, S) transition slots on
        the mega path — "float32" (default) or "bfloat16" (halves slot
        memory traffic; accumulation stays float32, drift is bounded by
        the mixed-precision test).
    """

    cfg: generative.AifConfig = dataclasses.field(
        default_factory=generative.AifConfig)
    disc: spaces.DiscretizationConfig | None = None
    util_edges: tuple[float, ...] | None = None
    util_period: int = 10
    use_pallas: bool = False
    mega: bool = False
    mega_slot_dtype: str = "float32"

    name = "aif"

    def __post_init__(self):
        topo = self.cfg.topology
        disc = self.disc or spaces.DiscretizationConfig()
        if len(disc.modality_edges()) != topo.n_modalities:
            raise ValueError(
                f"DiscretizationConfig covers {len(disc.modality_edges())} "
                f"modalities but the topology declares {topo.n_modalities} "
                f"({topo.modalities}); pass disc with matching `edges` (and "
                f"an env_step whose raw_obs has one column per modality)")
        edges = (topo.util_edges if self.util_edges is None
                 else tuple(self.util_edges))
        if len(edges) != topo.n_levels - 1:
            raise ValueError(
                f"util_edges needs {topo.n_levels - 1} edges for "
                f"{topo.n_levels}-level state factors, got {edges} "
                f"(out-of-range bins would make the utilization scrape "
                f"match no state)")
        if "error" not in topo.modalities:
            raise ValueError(
                f"topology modalities {topo.modalities} lack 'error': the "
                f"adaptive-preference EMA (paper §4.2) is driven by the "
                f"error modality's raw value — without it the fleet router "
                f"would silently track an unrelated telemetry column")
        if self.mega:
            if self.period % self.dwell != 0:
                raise ValueError(
                    f"mega=True needs the dwell ({self.dwell} ticks) to "
                    f"divide the slow period ({self.period} ticks): the "
                    f"megakernel compiles the selecting/held tick structure "
                    f"statically per window")
            if self.cfg.novelty_weight != 0.0:
                raise ValueError(
                    "mega=True does not implement the beyond-paper novelty "
                    "bonus (novelty_weight != 0) — the factored window "
                    "drops it; run the per-tick path (mega=False) instead")
        elif self.use_pallas:
            raise ValueError(
                "use_pallas=True selects the Pallas megakernel, which only "
                "the mega engine runs; set mega=True as well (the per-tick "
                "engine has no kernel to dispatch)")
        if self.mega_slot_dtype not in ("float32", "bfloat16"):
            raise ValueError(
                f"mega_slot_dtype must be 'float32' or 'bfloat16', got "
                f"{self.mega_slot_dtype!r}")

    # ------------------------------------------------------- engine hints
    @property
    def n_tiers(self) -> int:
        return self.cfg.topology.n_tiers

    @property
    def n_modalities(self) -> int:
        return self.cfg.topology.n_modalities

    @property
    def period(self) -> int:
        return max(int(self.cfg.slow_period_s / self.cfg.fast_period_s), 1)

    @property
    def dwell(self) -> int:
        return max(int(self.cfg.action_dwell_s / self.cfg.fast_period_s), 1)

    @property
    def has_slow(self) -> bool:
        return True

    # Evidence-assembly statics the whole-window engine path inlines into
    # the megakernel window (the per-tick paths consume them via _observe).
    @property
    def resolved_disc(self) -> spaces.DiscretizationConfig:
        return self.disc or spaces.DiscretizationConfig()

    @property
    def resolved_util_edges(self) -> tuple[float, ...]:
        topo = self.cfg.topology
        return (topo.util_edges if self.util_edges is None
                else tuple(self.util_edges))

    def clock_phase(self, carry) -> int | None:
        t = carry.t
        if isinstance(t, jax.core.Tracer):
            raise ValueError(
                "the rollout cannot infer the fleet clock from a traced "
                "agent state; pass t0= explicitly (the number of fast ticks "
                "already elapsed — 0 for a fresh fleet).  Without it the "
                "dwell/slow schedules would compile against the wrong "
                "phase and silently freeze action selection.")
        vals = np.unique(np.asarray(t))
        # mixed clocks -> None: the engine falls back to the flat safe scan
        return int(vals[0]) % self.period if vals.size == 1 else None

    # --------------------------------------------------------- transitions
    def init_carry(self, r: int) -> agent_mod.AgentState:
        return fleet_mod.init_fleet_state(self.cfg, r)

    def _observe(self, obs: RouterObs):
        """Shared evidence assembly: discretize the published telemetry and
        the 10 s utilization scrape (tier order -> state-factor order)."""
        topo = self.cfg.topology
        obs_bins = spaces.discretize_observation(obs.raw_obs,
                                                 self.resolved_disc)
        edges = jnp.asarray(self.resolved_util_edges, jnp.float32)
        util_hml = obs.tier_utilization[:, ::-1]
        util_bins = jnp.sum(util_hml[..., None] >= edges,
                            axis=-1).astype(jnp.int32)
        util_valid = ((obs.t_idx % self.util_period) == 0) & (obs.t_idx > 0)
        err_ix = topo.modalities.index("error")   # pinned by __post_init__
        return obs_bins, util_bins, util_valid, obs.raw_obs[:, err_ix]

    def _watchdog(self, carry):
        """Quarantine-and-reinit diverged cells on the incoming carry.

        The check runs *before* the tick so a poisoned cell is healed before
        its state flows into this tick's belief/EFE math; the ``lax.cond``
        identity branch keeps a healthy fleet's program bit-identical to
        ``cfg.watchdog=False``.  Returns (carry, (R,) float 0/1 events).
        """
        bad = fleet_mod.fleet_watchdog_bad(carry)
        carry = jax.lax.cond(
            jnp.any(bad),
            lambda c: fleet_mod.fleet_quarantine(c, bad, self.cfg),
            lambda c: c, carry)
        return carry, bad.astype(jnp.float32)

    def step(self, carry, obs, obs_mask, keys):
        wd = None
        if self.cfg.watchdog:
            carry, wd = self._watchdog(carry)
        obs_bins, util_bins, util_valid, raw_err = self._observe(obs)
        carry, info = fleet_mod.fleet_fast_step(
            carry, obs_bins, raw_err, keys, self.cfg, util_bins, util_valid,
            obs_mask)
        return carry, info.routing_weights, TickInfo(action=info.action,
                                                     unstable=info.unstable,
                                                     watchdog=wd)

    def light_step(self, carry, obs, obs_mask):
        wd = None
        if self.cfg.watchdog:
            carry, wd = self._watchdog(carry)
        obs_bins, util_bins, util_valid, raw_err = self._observe(obs)
        carry, info = fleet_mod.fleet_light_step(
            carry, obs_bins, raw_err, self.cfg, util_bins, util_valid,
            obs_mask)
        return carry, info.routing_weights, TickInfo(action=info.action,
                                                     unstable=info.unstable,
                                                     watchdog=wd)

    def slow_step(self, carry, keys):
        return fleet_mod.fleet_slow_step(carry, keys, self.cfg)
