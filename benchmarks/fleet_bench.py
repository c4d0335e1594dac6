"""Fleet scenario-engine benchmark: cell-windows/sec vs fleet size R.

Workloads, all single jitted ``lax.scan`` programs (no Python in the loop):

* ``env``          — the batched fluid engine alone under a static router
                     (R × T cell-windows per rollout; the R=256 × T=600 row
                     is the acceptance workload of the fleet engine),
* ``fleet_vmap``   — the full closed loop (belief update → EFE → action →
                     once-per-period online learning + fluid engine step per
                     window) with the vmapped per-router EFE einsums,
* ``fleet_fused``  — same loop with belief update + EFE fused into one
                     (R, A, S, S) launch (XLA oracle),
* ``fleet_fused_pallas`` — the fused launch dispatched to the Pallas kernel
                     (``--use-pallas``; interpret-mode emulation off-TPU, so
                     off by default — it benchmarks the emulator, not the
                     kernel),
* ``fleet_mega``     — the whole-window megakernel engine path (one fused
                     launch per slow period: belief → EFE → sampling →
                     dwell → env window, factored transition slots — see
                     ``repro.core.mega``); the XLA oracle twin of the
                     Pallas megakernel, so the row tracks the production
                     CPU path and the kernel's algorithm at once,
* ``api_compare``    — the declarative ``repro.api.compare`` surface
                     end-to-end (AIF + uniform pair, config assembly and
                     host-side summary included), guarding the public
                     Experiment entry point,
* ``fleet_sharded``  — (``--shard``) the device-sharded closed loop under
                     ``shard_map``, weak scaling at fixed cells/device over
                     1/2/4 devices, plus a roofline line for the compiled
                     per-device tick,
* ``fleet_mega_sharded`` — (``--shard``) the whole-window megakernel path
                     under the same mesh: each shard runs the super-launch
                     over its row block (draw-at-true-R PRNG contract) and
                     the metrics reducer folds whole windows at once; the
                     weak-scaling twin of ``fleet_mega``.

``--profile`` breaks the megakernel rollout's wall clock into its dispatch
phases (single super-launch vs per-period chunked launches vs the slow
boundary) and, given a directory, wraps the run in a ``jax.profiler`` trace
for TensorBoard/Perfetto drill-down.

Each path is recorded as a separate entry in the repo-root
``BENCH_fleet.json`` (schema ``{benchmark, device, entries: [{name, config,
cell_windows_per_s, wall_s}]}``; ``config`` carries the scenario so rows
from different scenarios never collide) so the perf trajectory tracks the
kernel path being optimized, not just the environment engine.  CI gates on
it via ``benchmarks/check_perf_regression.py``.

``--scenario`` selects the scenario driving the closed-loop fleet rows
(default ``paper-burst``); a ``flaky-telemetry`` fused row is always
recorded as well, tracking the masked partial-observability path's cost.

``--roofline`` additionally lowers the env, fused and megakernel rollouts,
prices their optimized HLO against the fixed accelerator model of
``repro.launch.roofline`` (197 TFLOP/s bf16, 819 GB/s HBM) and records
attained-vs-peak rows under the ``"roofline"`` key of ``BENCH_fleet.json``
— the arithmetic-intensity trajectory of the kernel lineage, independent
of the host the bench ran on.

Reports compile time and steady-state throughput per configuration as CSV on
stdout; ``--json out.json`` additionally writes the raw rows for the CI
benchmark artifact.

    PYTHONPATH=src python benchmarks/fleet_bench.py [--quick] [--json PATH]
                                                    [--scenario NAME]
                                                    [--use-pallas]
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import time

import jax
import jax.numpy as jnp

from repro import api
from repro.compile_cache import enable_compile_cache
from repro.core import AifConfig, fleet
from repro.envsim import SimConfig, batched, scenarios


def _bench(make_args, run, iters: int = 3,
           min_time_s: float = 0.5) -> tuple[float, float]:
    """(compile_s, steady_run_s) for a jitted rollout callable.

    ``make_args`` builds fresh inputs per iteration (outside the timed
    window): the fleet rollout donates its state buffers, so inputs cannot
    be reused across calls.  Sub-second workloads keep iterating until
    ``min_time_s`` of measured run time accumulates — the env row is the
    machine-speed anchor for the CI regression gate, so its measurement
    must not be a single ~0.1 s sample.
    """
    args = make_args()
    t0 = time.perf_counter()
    jax.block_until_ready(run(*args))
    compile_s = time.perf_counter() - t0
    total, n = 0.0, 0
    while n < iters or (total < min_time_s and n < 50):
        args = make_args()
        jax.block_until_ready(args)
        t0 = time.perf_counter()
        jax.block_until_ready(run(*args))
        total += time.perf_counter() - t0
        n += 1
    return compile_s, total / n


def bench_env(r: int, t: int, scenario: str = "paper-burst") -> dict:
    """Static-router fluid rollout at (R, T)."""
    cfg = SimConfig()
    sc = scenarios.build_scenario(scenario, cfg, r, t)
    params = batched.params_from_config(cfg, r, sc.capacity_scale)
    rate = jnp.asarray(sc.arrival_rate)
    hz = jnp.asarray(sc.hazard_scale)
    w = jnp.asarray([0.15, 0.23, 0.62], jnp.float32)
    key = jax.random.key(0)

    compile_s, run_s = _bench(
        tuple, lambda: batched.run_fluid(params, rate, hz, w, key))
    return {
        "workload": "env", "r": r, "t": t, "scenario": scenario,
        "compile_s": round(compile_s, 3),
        "run_s": round(run_s, 4),
        "cell_windows_per_s": round(r * t / run_s, 1),
    }


def bench_fleet(r: int, t: int, fused: bool, use_pallas: bool = False,
                scenario: str = "paper-burst", watchdog: bool = True) -> dict:
    """Closed-loop AIF fleet rollout at (R, T) under a named scenario.

    ``watchdog=False`` benchmarks the same loop with the in-scan numerical
    watchdog compiled out (``_nowd`` row name).  The CI overhead gate's
    fused/nowd pair comes from :func:`bench_fleet_pair` instead, whose
    interleaved timing makes the ratio drift-immune.
    """
    cfg = AifConfig(watchdog=watchdog)
    scfg = SimConfig()
    sc = scenarios.build_scenario(scenario, scfg, r, t)
    params = batched.params_from_config(scfg, r, sc.capacity_scale)
    env_step = batched.make_scenario_env_step(params, sc)
    key = jax.random.key(0)
    router = api.AifRouter(cfg=cfg, fused=fused, use_pallas=use_pallas)

    def make_args():
        # fresh per iteration: the rollout donates both state pytrees
        return (fleet.init_fleet_state(cfg, r),
                batched.init_fluid_state(params))

    compile_s, run_s = _bench(
        make_args,
        lambda ast, est: api.rollout(router, ast, est, env_step, t, key))
    name = "fleet_" + ("fused_pallas" if fused and use_pallas
                       else "fused" if fused else "vmap")
    if not watchdog:
        name += "_nowd"
    return {
        "workload": name, "r": r, "t": t, "scenario": scenario,
        "compile_s": round(compile_s, 3),
        "run_s": round(run_s, 4),
        "cell_windows_per_s": round(r * t / run_s, 1),
    }


def bench_fleet_pair(r: int, t: int, scenario: str = "paper-burst",
                     iters: int = 3) -> list[dict]:
    """Fused closed loop with the watchdog on and compiled out, interleaved.

    ``check_perf_regression`` gates the *ratio* of these two rows (clean-path
    watchdog overhead ≤ 10 %), and a ratio of rows timed minutes apart lets
    machine drift — thermal throttling, noisy neighbors — masquerade as
    watchdog cost (observed ±15 % swings in both directions on a shared
    2-core host).  So the pair is measured back-to-back: alternating
    iterations from the same wall-clock window, best-of-``iters`` each,
    which cancels drift and lets the minimum discard contended samples.
    """
    scfg = SimConfig()
    sc = scenarios.build_scenario(scenario, scfg, r, t)
    params = batched.params_from_config(scfg, r, sc.capacity_scale)
    env_step = batched.make_scenario_env_step(params, sc)
    key = jax.random.key(0)
    routers = {wd: api.AifRouter(cfg=AifConfig(watchdog=wd), fused=True)
               for wd in (True, False)}

    def once(router) -> float:
        # fresh per call: the rollout donates both state pytrees
        args = (fleet.init_fleet_state(router.cfg, r),
                batched.init_fluid_state(params))
        jax.block_until_ready(args)
        t0 = time.perf_counter()
        jax.block_until_ready(api.rollout(router, *args, env_step, t, key))
        return time.perf_counter() - t0

    compile_s = {wd: once(router) for wd, router in routers.items()}
    best = {wd: float("inf") for wd in routers}
    for _ in range(iters):
        for wd, router in routers.items():
            best[wd] = min(best[wd], once(router))
    return [{
        "workload": "fleet_fused" + ("" if wd else "_nowd"),
        "r": r, "t": t, "scenario": scenario,
        "compile_s": round(compile_s[wd], 3),
        "run_s": round(best[wd], 4),
        "cell_windows_per_s": round(r * t / best[wd], 1),
    } for wd in (True, False)]


def bench_mega(r: int, t: int, use_pallas: bool = False,
               scenario: str = "paper-burst") -> dict:
    """Whole-window megakernel closed loop at (R, T): one launch per slow
    period, env fused into the window.  Always a fresh fleet (mega carries
    own their clock), so ``carry=None`` and only the env state is rebuilt
    per iteration."""
    sc_cfg = SimConfig()
    sc = scenarios.build_scenario(scenario, sc_cfg, r, t)
    params = batched.params_from_config(sc_cfg, r, sc.capacity_scale)
    env_step = batched.make_scenario_env_step(params, sc)
    key = jax.random.key(0)
    router = api.AifRouter(cfg=AifConfig(), fused=True, mega=True,
                           use_pallas=use_pallas)

    def make_args():
        return (batched.init_fluid_state(params),)

    compile_s, run_s = _bench(
        make_args,
        lambda est: api.rollout(router, None, est, env_step, t, key))
    name = "fleet_mega_pallas" if use_pallas else "fleet_mega"
    return {
        "workload": name, "r": r, "t": t, "scenario": scenario,
        "compile_s": round(compile_s, 3),
        "run_s": round(run_s, 4),
        "cell_windows_per_s": round(r * t / run_s, 1),
    }


def bench_graph(r: int, t: int, preset: str) -> dict:
    """Closed-loop fused AIF rollout on a graphed world (networked
    continuum): spillover segment-sums + the neighbor-pressure modality on
    the per-tick engine path.  ``preset`` is a ``repro.core.graph`` preset
    name; the matching graph scenario drives the load shape, so these rows
    never collide with the ungraphed grid in BENCH_fleet.json.
    """
    from repro.api.experiment import _build_world, _make_aif
    from repro.core import graph as graph_mod
    from repro.core.topology import default_topology

    sc_name = {v: k for k, v in graph_mod.GRAPH_SCENARIOS.items()}[preset]
    topo = default_topology()
    g = graph_mod.GRAPH_PRESETS[preset](r)
    scfg, params, env_step = _build_world(topo, sc_name, r, t, 1.0, 0, g)
    router = _make_aif(topo, scfg, True, False, False, graph=g)
    key = jax.random.key(0)

    def make_args():
        return (router.init_carry(r),
                batched.init_fluid_state(
                    params, n_modalities=env_step.n_obs_modalities))

    compile_s, run_s = _bench(
        make_args,
        lambda ast, est: api.rollout(router, ast, est, env_step, t, key))
    return {
        "workload": "fleet_graph", "r": r, "t": t, "scenario": sc_name,
        "graph": preset,
        "compile_s": round(compile_s, 3),
        "run_s": round(run_s, 4),
        "cell_windows_per_s": round(r * t / run_s, 1),
    }


def run_graph(quick: bool = False) -> list[dict]:
    """``--graph`` rows: the graphed closed loop at the ring and grid
    presets (R ∈ {64, 256}; quick mode keeps the 64-cell pair)."""
    rows = []
    sizes = [64] if quick else [64, 256]
    for preset in ("ring", "grid"):
        for r in sizes:
            rows.append(bench_graph(r, 120, preset))
            _print_row(rows[-1])
    return rows


def bench_api_compare(r: int, t: int, scenario: str = "paper-burst") -> dict:
    """The declarative comparison surface end-to-end: ``repro.api.compare``
    over an AIF + uniform pair, including the config assembly and host-side
    summary the Experiment API owns.  Guards the new public entry point the
    same way the raw rollout rows guard the engine."""
    exps = [api.Experiment(router=name, scenario=scenario, n_cells=r,
                           n_windows=t, fused=(name == "aif"))
            for name in ("aif", "uniform")]

    compile_s, run_s = _bench(tuple, lambda: api.compare(exps))
    return {
        "workload": "api_compare", "r": r, "t": t, "scenario": scenario,
        "compile_s": round(compile_s, 3),
        "run_s": round(run_s, 4),
        "cell_windows_per_s": round(len(exps) * r * t / run_s, 1),
    }


def bench_sharded(r_local: int, t: int, devices: int,
                  scenario: str = "paper-burst") -> dict:
    """Device-sharded closed loop at weak scaling: R = r_local × devices.

    The fused AIF router under ``shard_map`` with on-device metric
    reduction (:func:`repro.api.engine.sharded_rollout`) — per-device work
    is constant across the curve, so on real parallel hardware the wall
    clock should stay flat as R grows with the mesh.  On a single-core
    host with virtual devices the row instead measures the sharding
    machinery's overhead honestly (devices time-share the core).
    """
    from repro.api import engine as engine_mod
    from repro.api.experiment import FleetMetricsReducer, _build_world_padded
    from repro.core.topology import default_topology

    r = r_local * devices
    spec = api.ShardSpec(devices=devices)
    _, params, env_step = _build_world_padded(
        default_topology(), scenario, r, t, 1.0, 0, r, devices)
    router = api.AifRouter(cfg=AifConfig(), fused=True)
    reducer = FleetMetricsReducer(n_cells=r)
    key = jax.random.key(0)

    def make_args():
        return (batched.init_fluid_state(params),)

    compile_s, run_s = _bench(
        make_args,
        lambda est: engine_mod.sharded_rollout(
            router, est, env_step, t, key, shard=spec, n_cells=r,
            reducer=reducer))
    return {
        "workload": "fleet_sharded", "r": r, "t": t, "scenario": scenario,
        "devices": devices, "host_cores": os.cpu_count() or 1,
        "compile_s": round(compile_s, 3),
        "run_s": round(run_s, 4),
        "cell_windows_per_s": round(r * t / run_s, 1),
    }


def bench_mega_sharded(r_local: int, t: int, devices: int,
                       scenario: str = "paper-burst") -> dict:
    """Device-sharded whole-window megakernel at weak scaling.

    The mega router through :func:`repro.api.engine.sharded_rollout`: one
    super-launch per shard over its row block, window-level metric
    reduction on device.  Same mesh/key contract as :func:`bench_sharded`,
    so the pair of curves prices exactly the engine-path swap the sharded
    fleet gets from ``Experiment(mega=True, shard="auto")``.
    """
    from repro.api import engine as engine_mod
    from repro.api.experiment import FleetMetricsReducer, _build_world_padded
    from repro.core.topology import default_topology

    r = r_local * devices
    spec = api.ShardSpec(devices=devices)
    _, params, env_step = _build_world_padded(
        default_topology(), scenario, r, t, 1.0, 0, r, devices)
    router = api.AifRouter(cfg=AifConfig(), fused=True, mega=True)
    reducer = FleetMetricsReducer(n_cells=r)
    key = jax.random.key(0)

    def make_args():
        return (batched.init_fluid_state(params),)

    compile_s, run_s = _bench(
        make_args,
        lambda est: engine_mod.sharded_rollout(
            router, est, env_step, t, key, shard=spec, n_cells=r,
            reducer=reducer))
    return {
        "workload": "fleet_mega_sharded", "r": r, "t": t,
        "scenario": scenario, "devices": devices,
        "host_cores": os.cpu_count() or 1,
        "compile_s": round(compile_s, 3),
        "run_s": round(run_s, 4),
        "cell_windows_per_s": round(r * t / run_s, 1),
    }


def profile_mega(r: int, t: int, scenario: str = "paper-burst",
                 trace_dir: str | None = None) -> None:
    """Per-phase wall breakdown of the megakernel rollout.

    Times the same rollout three ways on one warm process:

    * the single super-launch (one dispatch for all T windows),
    * chunked per-period launches (``launch_periods=1`` — the PR-7
      dispatch cadence), whose excess over the super-launch is the host
      dispatch gap the super-launch eliminated,
    * the slow boundary alone (jitted :func:`repro.core.mega.mega_slow_step`
      on the final state), scaled by the number of boundaries.

    With ``trace_dir`` the super-launch run is additionally wrapped in a
    ``jax.profiler`` trace (open with TensorBoard's profile plugin or
    Perfetto) for op-level drill-down.
    """
    from repro.core.mega import mega_slow_step

    sc_cfg = SimConfig()
    sc = scenarios.build_scenario(scenario, sc_cfg, r, t)
    params = batched.params_from_config(sc_cfg, r, sc.capacity_scale)
    env_step = batched.make_scenario_env_step(params, sc)
    key = jax.random.key(0)
    router = api.AifRouter(cfg=AifConfig(), fused=True, mega=True)
    period = router.period
    n_bound = t // period

    def timed(launch_periods=None):
        est = batched.init_fluid_state(params)
        jax.block_until_ready(est)
        t0 = time.perf_counter()
        out = api.rollout(router, None, est, env_step, t, key,
                          launch_periods=launch_periods)
        jax.block_until_ready(out)
        return time.perf_counter() - t0, out

    # warm both programs, then measure
    _, (state, _, _) = timed()
    timed(launch_periods=1)
    single_s, _ = timed()
    chunked_s, _ = timed(launch_periods=1)

    slow = jax.jit(lambda s, k: mega_slow_step(s, k, router.cfg))
    keys = jax.random.split(jax.random.key(1), r)
    jax.block_until_ready(slow(state, keys))
    t0 = time.perf_counter()
    jax.block_until_ready(slow(state, keys))
    slow_s = (time.perf_counter() - t0) * n_bound

    gap = chunked_s - single_s
    print(f"profile[fleet_mega r={r} t={t} scenario={scenario}]:")
    print(f"  super-launch (1 dispatch)      {single_s * 1e3:9.2f} ms "
          f"({r * t / single_s:,.0f} cw/s)")
    print(f"  chunked, launch_periods=1      {chunked_s * 1e3:9.2f} ms "
          f"over {n_bound} launches")
    print(f"  host dispatch gap eliminated   {gap * 1e3:9.2f} ms "
          f"({gap / max(n_bound, 1) * 1e3:.3f} ms/launch)")
    print(f"  slow boundary (streamed)       {slow_s * 1e3:9.2f} ms "
          f"total across {n_bound} boundaries "
          f"({100 * slow_s / max(single_s, 1e-12):.1f}% of super-launch "
          f"wall)", flush=True)
    if trace_dir:
        with jax.profiler.trace(trace_dir):
            timed()
        print(f"profiler trace written to {trace_dir} (open with "
              f"TensorBoard's profile plugin or ui.perfetto.dev)",
              flush=True)


def _sharded_roofline(r_local: int, t: int, devices: int,
                      scenario: str = "paper-burst") -> None:
    """Print roofline terms for the compiled sharded tick (per-device HLO)."""
    from repro.api import engine as engine_mod
    from repro.api.experiment import FleetMetricsReducer, _build_world_padded
    from repro.core.topology import default_topology
    from repro.launch import hlo_cost, roofline

    r = r_local * devices
    spec = api.ShardSpec(devices=devices)
    _, params, env_step = _build_world_padded(
        default_topology(), scenario, r, t, 1.0, 0, r, devices)
    router = api.AifRouter(cfg=AifConfig(), fused=True)
    compiled = engine_mod._sharded_impl.lower(
        batched.init_fluid_state(params), jax.random.key(0), router=router,
        env_step=env_step, n_steps=t, obs_masked=False, clock_phase=0,
        spec=spec, n_cells=r, reducer=FleetMetricsReducer(n_cells=r)
    ).compile()
    text = compiled.as_text()
    st = hlo_cost.analyze_text(text)
    coll = roofline.parse_collectives(text, default_group=devices)
    per_win = st.flops / t
    print(f"roofline[fleet_sharded r={r} t={t} d={devices}]: "
          f"{st.flops / 1e9:.2f} GFLOP/device ({per_win / 1e6:.1f} MFLOP per "
          f"window), {st.hbm_bytes / 1e9:.2f} GB HBM, "
          f"intensity {st.flops / max(st.hbm_bytes, 1.0):.2f} FLOP/B, "
          f"collectives {sum(coll.counts.values())} ops / "
          f"{coll.link_bytes / 1e3:.1f} kB link", flush=True)


def _lowered_workloads(scenario: str = "paper-burst") -> dict[str, tuple]:
    """(compiled, r, t) per kernel-lineage workload, for roofline pricing.

    Lowers the same jitted programs the bench rows time — the env engine
    alone, the fused per-tick closed loop, and the whole-window megakernel
    — at the CI comparison shapes, and compiles without running.
    """
    from repro.api import engine as engine_mod
    from repro.core import fleet as fleet_mod
    from repro.core.mega import init_mega_state

    out: dict[str, tuple] = {}
    # env: the batched fluid engine alone at the acceptance shape
    r, t = 256, 600
    cfg = SimConfig()
    sc = scenarios.build_scenario(scenario, cfg, r, t)
    params = batched.params_from_config(cfg, r, sc.capacity_scale)
    w = jnp.asarray([0.15, 0.23, 0.62], jnp.float32)
    env_fn = jax.jit(lambda p, a, h, ww, k: batched.run_fluid(p, a, h, ww, k))
    out["env"] = (env_fn.lower(params, jnp.asarray(sc.arrival_rate),
                               jnp.asarray(sc.hazard_scale), w,
                               jax.random.key(0)).compile(), r, t)
    # closed loops at the apples-to-apples comparison shape
    r, t = 64, 120
    cfg = SimConfig()
    sc = scenarios.build_scenario(scenario, cfg, r, t)
    params = batched.params_from_config(cfg, r, sc.capacity_scale)
    env_step = batched.make_scenario_env_step(params, sc)
    key = jax.random.key(0)
    acfg = AifConfig()
    fused = api.AifRouter(cfg=acfg, fused=True)
    out["fleet_fused"] = (engine_mod._rollout_impl.lower(
        fleet_mod.init_fleet_state(acfg, r), batched.init_fluid_state(params),
        env_step, t, key, router=fused).compile(), r, t)
    mega = api.AifRouter(cfg=acfg, fused=True, mega=True)
    fl = env_step.fluid
    state0 = init_mega_state(acfg, r, t)
    obs_carry = (jnp.zeros((r, mega.n_modalities), jnp.float32),
                 jnp.zeros((r, mega.n_tiers), jnp.float32),
                 jnp.ones((r, mega.n_tiers), jnp.float32),
                 jnp.zeros((r, mega.n_tiers), jnp.float32),
                 jnp.ones((r, mega.n_modalities), jnp.float32))
    out["fleet_mega"] = (engine_mod._mega_impl.lower(
        state0, batched.init_fluid_state(params), obs_carry, fl.params,
        fl.arrival_rate, fl.hazard_scale, fl.obs_valid, fl.forced_down,
        fl.speed, fl.graph, key, jnp.asarray(0, jnp.int32), router=mega,
        n_steps=t, obs_masked=False, dt=fl.dt, scrape_every=fl.scrape_every,
        restart_blackout=fl.restart_blackout).compile(), r, t)
    return out


def run_roofline(measured: list[dict],
                 scenario: str = "paper-burst") -> list[dict]:
    """Attained-vs-peak rows per kernel (env / fleet_fused / fleet_mega).

    Prices each compiled rollout's optimized HLO against the fixed
    accelerator model (197 TFLOP/s bf16, 819 GB/s HBM — see
    ``repro.launch.roofline``): per-rollout FLOPs, HBM traffic, arithmetic
    intensity and the modeled compute/memory bound.  When this bench run
    measured the matching throughput row, the attained FLOP/s and the
    fraction of the modeled roofline are attached — on a CPU host that
    fraction is honest about how far the XLA path sits from the model
    hardware; on a TPU it becomes the kernel's efficiency gate.
    """
    from repro.launch import hlo_cost
    from repro.launch import roofline as rl

    wall = {(row["workload"], row["r"], row["t"], row.get("scenario")):
            row["run_s"] for row in measured}
    rows = []
    for name, (compiled, r, t) in _lowered_workloads(scenario).items():
        st = hlo_cost.analyze_text(compiled.as_text())
        compute_s = st.flops / rl.PEAK_FLOPS
        memory_s = st.hbm_bytes / rl.HBM_BW
        bound_s = max(compute_s, memory_s)
        row = {
            "name": f"roofline_{name}",
            "config": {"r": r, "t": t, "scenario": scenario},
            "flops": st.flops,
            "hbm_bytes": st.hbm_bytes,
            "intensity_flop_per_byte": round(
                st.flops / max(st.hbm_bytes, 1.0), 3),
            "bound": "compute" if compute_s >= memory_s else "memory",
            "model_bound_s": bound_s,
            "model_cell_windows_per_s": round(r * t / max(bound_s, 1e-12), 1),
        }
        run_s = wall.get((name, r, t, scenario))
        if run_s:
            row["measured_wall_s"] = run_s
            row["attained_gflops"] = round(st.flops / run_s / 1e9, 3)
            row["pct_of_model_roofline"] = round(100 * bound_s / run_s, 4)
        rows.append(row)
        print(f"roofline[{name} r={r} t={t}]: "
              f"{st.flops / 1e9:.2f} GFLOP, {st.hbm_bytes / 1e9:.2f} GB HBM, "
              f"intensity {row['intensity_flop_per_byte']:.2f} FLOP/B, "
              f"{row['bound']}-bound {bound_s * 1e3:.3f} ms on model HW"
              + (f", attained {row['attained_gflops']:.1f} GFLOP/s "
                 f"({row['pct_of_model_roofline']:.3f}% of model roofline)"
                 if run_s else ""), flush=True)
    return rows


def run(quick: bool = False, use_pallas: bool = False,
        scenario: str = "paper-burst") -> list[dict]:
    rows = []
    # acceptance workload first: R=256 cells x T=600 windows, one jitted scan
    env_grid = [(256, 600)] if quick else [(16, 600), (64, 600), (256, 600),
                                           (1024, 600)]
    for r, t in env_grid:
        rows.append(bench_env(r, t))
        _print_row(rows[-1])
    # closed loop: the (64, 120) vmap row pairs with the fused row below
    # for the apples-to-apples comparison CI gates on; the full run adds
    # the acceptance-scale fused rollout (R=256 x T=600).
    fleet_grid = ([(64, 120, False)] if quick else
                  [(64, 120, False), (256, 600, True)])
    for r, t, fused in fleet_grid:
        rows.append(bench_fleet(r, t, fused, scenario=scenario))
        _print_row(rows[-1])
    # the (64, 120) fused row and its watchdog-free twin, interleaved so
    # the overhead ratio check_perf_regression gates is drift-immune
    for row in bench_fleet_pair(64, 120, scenario=scenario):
        rows.append(row)
        _print_row(row)
    # whole-window megakernel path: the (64, 120) row pairs with the fused
    # row above for the speedup gate; the full run adds the paper-burst
    # acceptance shape (R=64 x T=120 is also the --quick row, so quick-mode
    # CI gates the megakernel's trajectory too).
    mega_grid = [(64, 120)] if quick else [(64, 120), (256, 600)]
    for r, t in mega_grid:
        rows.append(bench_mega(r, t, scenario=scenario))
        _print_row(rows[-1])
    # masked partial-observability path (always recorded: tracks the cost of
    # the mask-aware belief/EFE/learning plumbing vs the clean rows above)
    if scenario != "flaky-telemetry":
        rows.append(bench_fleet(64, 120, fused=True,
                                scenario="flaky-telemetry"))
        _print_row(rows[-1])
    # declarative Experiment surface (always recorded: guards repro.api)
    rows.append(bench_api_compare(64, 120))
    _print_row(rows[-1])
    if use_pallas:
        rows.append(bench_fleet(16, 60, fused=True, use_pallas=True,
                                scenario=scenario))
        _print_row(rows[-1])
        sc = scenarios.build_scenario(scenario, SimConfig(), 4, 20)
        if sc.forced_down is None and sc.speed is None:
            rows.append(bench_mega(4, 20, use_pallas=True,
                                   scenario=scenario))
            _print_row(rows[-1])
        else:
            print(f"fleet_mega_pallas: not run — {scenario!r} injects "
                  f"faults, which the Pallas megakernel does not implement")
    return rows


def run_shard(quick: bool = False, scenario: str = "paper-burst",
              r_local: int = 64, t: int = 120) -> list[dict]:
    """Weak-scaling curves of the device-sharded closed loops.

    Fixed cells-per-device, device counts 1 / 2 / 4 (capped at what is
    local — run under ``XLA_FLAGS=--xla_force_host_platform_device_count=4``
    for the full curve on CPU), per-tick (``fleet_sharded``) and megakernel
    (``fleet_mega_sharded``) engine paths.  ``--quick`` drops the middle
    point; the endpoints keep the same (name, r, t, scenario) keys as the
    full curve so the CI regression gate matches them against the committed
    rows.
    """
    avail = jax.local_device_count()
    counts = [d for d in (1, 2, 4) if d <= avail]
    if quick and len(counts) > 2:
        counts = [counts[0], counts[-1]]
    # env acceptance row first: the machine-speed anchor
    # check_perf_regression calibrates the fleet_sharded rows against.
    rows = [bench_env(256, 600)]
    _print_row(rows[0])
    for d in counts:
        rows.append(bench_sharded(r_local, t, d, scenario=scenario))
        _print_row(rows[-1])
    for d in counts:
        rows.append(bench_mega_sharded(r_local, t, d, scenario=scenario))
        _print_row(rows[-1])
    _sharded_roofline(r_local, t, counts[-1], scenario=scenario)
    return rows


def _print_row(row: dict) -> None:
    print(f"{row['workload']},r={row['r']},t={row['t']},"
          f"scenario={row.get('scenario', '-')},"
          f"compile={row['compile_s']}s,run={row['run_s']}s,"
          f"{row['cell_windows_per_s']}cw/s", flush=True)


def _bench_summary(rows: list[dict], existing: dict | None = None,
                   roofline_rows: list[dict] | None = None) -> dict:
    """Repo-root BENCH_fleet.json: one entry per (workload path, R × T,
    scenario) configuration, so the CI regression gate can match quick-mode
    runs against the committed trajectory entry-by-entry.

    Entries *merge* into ``existing`` (matched on that key): a quick-mode
    run refreshes only the rows it measured instead of dropping the
    committed full-grid trajectory.  Entries carried over unmeasured are
    tagged ``"carried": true`` so the regression gate never mistakes a
    stale copy for a fresh measurement (``check_perf_regression`` drops
    carried rows on both sides).  Rows whose workload/config no longer
    exists are carried forever — prune them by hand when retiring a
    benchmark configuration.
    """
    def key(e):
        cfg = e.get("config", {})
        return (e["name"], cfg.get("r"), cfg.get("t"), cfg.get("scenario"))

    merged: dict[tuple, dict] = {}
    for e in (existing or {}).get("entries", []):
        merged[key(e)] = dict(e, carried=True)
    for row in rows:
        cfg = {"r": row["r"], "t": row["t"],
               "scenario": row.get("scenario")}
        if "graph" in row:
            cfg["graph"] = row["graph"]
        if "devices" in row:
            cfg["devices"] = row["devices"]
        if "host_cores" in row:
            cfg["host_cores"] = row["host_cores"]
        entry = {
            "name": row["workload"],
            "config": cfg,
            "cell_windows_per_s": row["cell_windows_per_s"],
            "wall_s": row["run_s"],
        }
        merged[key(entry)] = entry
    out = {
        "benchmark": "fleet_bench",
        "device": str(jax.devices()[0]),
        "entries": list(merged.values()),
    }
    # roofline rows are HLO-derived (machine-independent): a run without
    # --roofline carries the committed section forward unchanged.
    roof = roofline_rows or (existing or {}).get("roofline")
    if roof:
        out["roofline"] = roof
    return out


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="CI smoke subset (acceptance workload only)")
    ap.add_argument("--json", metavar="PATH",
                    help="write rows as JSON for the benchmark artifact")
    ap.add_argument("--scenario", default="paper-burst",
                    choices=sorted(scenarios.SCENARIOS),
                    help="scenario driving the closed-loop fleet rows")
    ap.add_argument("--use-pallas", action="store_true",
                    help="also benchmark the fused Pallas kernel path "
                         "(interpret-mode emulation off-TPU)")
    ap.add_argument("--roofline", action="store_true",
                    help="price the env / fused / megakernel rollouts "
                         "against the fixed accelerator model and record "
                         "attained-vs-peak rows in BENCH_fleet.json")
    ap.add_argument("--graph", action="store_true",
                    help="also benchmark the networked-continuum graphed "
                         "closed loop (fleet_graph rows at the ring/grid "
                         "presets)")
    ap.add_argument("--shard", action="store_true",
                    help="device-sharded weak-scaling curves (fleet_sharded "
                         "+ fleet_mega_sharded rows) instead of the standard "
                         "grid; use "
                         "XLA_FLAGS=--xla_force_host_platform_device_count=4"
                         " for the full CPU curve")
    ap.add_argument("--profile", nargs="?", const="", metavar="TRACE_DIR",
                    help="per-phase wall breakdown of the megakernel "
                         "rollout (super-launch vs chunked dispatch vs slow "
                         "boundary); pass a directory to also record a "
                         "jax.profiler trace there")
    args = ap.parse_args()
    if args.profile is not None:
        profile_mega(64, 120, scenario=args.scenario,
                     trace_dir=args.profile or None)
    if args.json:     # fail fast on an unwritable path, not after the bench
        open(args.json, "a").close()
    rows = (run_shard(quick=args.quick, scenario=args.scenario)
            if args.shard else
            run(quick=args.quick, use_pallas=args.use_pallas,
                scenario=args.scenario))
    if args.graph:
        rows += run_graph(quick=args.quick)
    roofline_rows = (run_roofline(rows, scenario=args.scenario)
                     if args.roofline else None)
    if args.json:
        bench_path = pathlib.Path(__file__).resolve().parent.parent / (
            "BENCH_fleet.json")
        # read the committed summary BEFORE writing the artifact: if --json
        # points at BENCH_fleet.json itself the artifact write would clobber
        # the entries the merge is meant to carry
        existing = None
        if bench_path.exists():
            with open(bench_path) as f:
                existing = json.load(f)
        with open(args.json, "w") as f:
            json.dump({"benchmark": "fleet_bench",
                       "device": str(jax.devices()[0]),
                       "rows": rows}, f, indent=2)
        print(f"wrote {args.json}")
        with open(bench_path, "w") as f:
            json.dump(_bench_summary(rows, existing, roofline_rows),
                      f, indent=2)
        print(f"wrote {bench_path}")


if __name__ == "__main__":
    main()
