"""Run the AIF mega fleet end to end on a TPU, with the compiled Pallas
megakernel, and check what comes out.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # the sharded mega path on a 2x2 mesh

One chip, in order:

1. precision: whether a default-precision float32 matmul rounds a one-hot
   gather on this chip (the kernel and the XLA oracle contract at HIGHEST).
2. small parity: ``Experiment(router="aif", mega=True)`` at R=8, T=20 with
   the compiled kernel against the XLA mega oracle — actions bit-equal,
   every trace field within 1e-4 (the interpret-mode parity contract).
3. deployment size: paper-burst on the paper topology (S=243, A=20) at
   R=2048 cells x T=600 windows, once with the kernel and once with the
   oracle.  Fleet success must agree within 0.5 points and P95 within 2%.

``--four-chips`` runs only the sharded phase: the mega fleet with
``shard="auto"`` at R=8192 (per-device peak memory must be even) and at
R=2048 against the unsharded run on one chip (metrics within the
sharded-mega test's 1e-4 bounds).  The sharded mega window is not ported to
the kernel, so this phase runs the XLA oracle.

Each phase prints one JSON line; the last line is
``{"ok": true, "device": {...}}``.  Any failed check or error exits
non-zero, and the script refuses to run anywhere but on a TPU.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
from repro import api  # noqa: E402
from repro.api import engine  # noqa: E402
from repro.api import experiment as experiment_mod  # noqa: E402
from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.core import mega as mega_core  # noqa: E402
from repro.envsim import batched  # noqa: E402

#: Fleet-level agreement of kernel and oracle at deployment size.  Both run
#: the same arithmetic at float32 but fuse and reassociate it differently,
#: so a cell whose categorical sample sits on a near-tie can take the other
#: action and follow its own trajectory from there; the bounds admit a few
#: such cells among 2048 and refuse a kernel that routes differently.
SUCCESS_PP = 0.5
P95_REL = 0.02
#: Sharded vs unsharded (``tests/test_mega.py``'s multi-device bounds).
SHARD_ATOL = 1e-4
#: Largest per-device peak over the smallest, for an even R=8192 split.
PEAK_SPREAD = 1.2

PARITY_FIELDS = ("routing_weights", "raw_obs", "unstable", "obs_frac")


def emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke: check failed: {what}")


def peak_bytes(device) -> int:
    return int(device.memory_stats()["peak_bytes_in_use"])


def precision_probe() -> None:
    """A one-hot row gather written as a matmul, at default and at HIGHEST
    precision, against the exact gather."""
    table = jax.random.uniform(jax.random.key(1), (256, 256)) + 1.0 / 3.0
    idx = jnp.arange(8) * 31
    onehot = jax.nn.one_hot(idx, 256, dtype=jnp.float32)
    exact = np.asarray(table[idx])
    default = np.asarray(jnp.dot(onehot, table))
    highest = np.asarray(jnp.dot(onehot, table,
                                 precision=jax.lax.Precision.HIGHEST))
    emit("precision",
         default_rounds_onehot_gather=bool(not np.array_equal(default,
                                                              exact)),
         default_max_abs_err=float(np.max(np.abs(default - exact))),
         highest_exact=bool(np.array_equal(highest, exact)))
    check(np.array_equal(highest, exact),
          "a HIGHEST-precision one-hot gather is not exact")


def trace_deviation(r_ref, r_new) -> dict:
    """Per-field max |difference| of two rollouts' traces, plus the count of
    differing actions (``tests/test_mega.py``'s rollout-parity checks)."""
    out = {"actions_differing": int(np.sum(
        np.asarray(r_ref.trace.actions) != np.asarray(r_new.trace.actions)))}
    pairs = [(n, getattr(r_ref.trace, n), getattr(r_new.trace, n))
             for n in PARITY_FIELDS]
    pairs += [(f"env.{f}", getattr(r_ref.trace.env, f),
               getattr(r_new.trace.env, f)) for f in r_ref.trace.env._fields
              if getattr(r_ref.trace.env, f) is not None]
    for name, a, b in pairs:
        out[name] = float(np.max(np.abs(np.asarray(a, np.float64)
                                        - np.asarray(b, np.float64))))
    return out


def mega_program(e):
    """Compile the unsharded mega rollout program that ``run(e)`` launches,
    for its compile time, its text and its memory analysis."""
    topo = e.resolve_topology()
    scfg, params, env_step = experiment_mod._build_world(
        topo, e.scenario, e.n_cells, e.n_windows, e.window_s, e.seed, None)
    router = e.resolve_router(scfg, None)
    fl = env_step.fluid
    r, m, k = e.n_cells, router.n_modalities, router.n_tiers
    n_mod = getattr(env_step, "n_obs_modalities", batched.N_OBS_MODALITIES)
    state = jax.eval_shape(
        lambda: mega_core.init_mega_state(router.cfg, r, e.n_windows))
    est = jax.eval_shape(
        lambda: batched.init_fluid_state(params, n_modalities=n_mod))
    obs = jax.eval_shape(lambda: engine._fresh_obs_carry(r, m, k))
    t0 = time.perf_counter()
    compiled = engine._mega_impl.lower(
        state, est, obs, fl.params, fl.arrival_rate, fl.hazard_scale,
        fl.obs_valid, fl.forced_down, fl.speed, fl.graph,
        jax.random.key(e.seed), jnp.asarray(0, jnp.int32), router=router,
        n_steps=e.n_windows, obs_masked=bool(env_step.emits_mask), dt=fl.dt,
        scrape_every=fl.scrape_every,
        restart_blackout=fl.restart_blackout).compile()
    return compiled, time.perf_counter() - t0


def small_parity() -> None:
    base = dict(router="aif", mega=True, n_cells=8, n_windows=20)
    ref = api.run(api.Experiment(**base))
    new = api.run(api.Experiment(**base, use_pallas=True))
    dev = trace_deviation(ref, new)
    emit("small_parity", n_cells=ref.experiment.n_cells,
         n_windows=ref.experiment.n_windows, **dev)
    check(dev["actions_differing"] == 0,
          "R=8 T=20: kernel actions differ from the oracle's")
    for name, err in dev.items():
        check(name == "actions_differing" or err <= 1e-4,
              f"R=8 T=20: {name} differs from the oracle by {err}")
    check(bool(np.all(np.isfinite(new.fluid.n_requests))),
          "R=8 T=20: non-finite request counts")


def deployment(device) -> None:
    results = {}
    for use_pallas in (True, False):
        e = api.Experiment(router="aif", scenario="paper-burst",
                           topology="paper-3tier", n_cells=2048,
                           n_windows=600, seed=0, mega=True,
                           use_pallas=use_pallas)
        compiled, compile_s = mega_program(e)
        has_kernel = "tpu_custom_call" in compiled.as_text()
        ma = compiled.memory_analysis()
        program_bytes = (ma.argument_size_in_bytes + ma.output_size_in_bytes
                         + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
        del compiled
        first = api.run(e)
        warm = api.run(e)
        cw = e.n_cells * e.n_windows
        emit("deployment", path="pallas" if use_pallas else "xla_oracle",
             n_cells=e.n_cells, n_windows=e.n_windows,
             compile_s=compile_s, first_run_s=first.wall_s,
             warm_run_s=warm.wall_s,
             cell_windows_per_s=cw / warm.wall_s,
             success_pct=warm.success_pct, p95_ms=warm.p95_ms,
             program_bytes=int(program_bytes),
             peak_bytes_in_use_so_far=peak_bytes(device),
             tpu_custom_call=has_kernel)
        check(bool(np.all(np.isfinite(warm.fluid.n_requests))
                   and np.all(np.isfinite(warm.fluid.p95_ms))),
              f"use_pallas={use_pallas}: non-finite fleet results")
        check(first.success_pct == warm.success_pct
              and first.p95_ms == warm.p95_ms,
              f"use_pallas={use_pallas}: two runs of one experiment differ")
        if use_pallas:
            check(has_kernel, "the Pallas megakernel is not in the compiled "
                  "program (no tpu_custom_call)")
        results[use_pallas] = warm
    pal, ref = results[True], results[False]
    d_succ = abs(pal.success_pct - ref.success_pct)
    d_p95 = abs(pal.p95_ms - ref.p95_ms) / ref.p95_ms
    emit("deployment_agreement", success_pp=d_succ, p95_rel=d_p95,
         success_bound_pp=SUCCESS_PP, p95_bound_rel=P95_REL)
    check(d_succ <= SUCCESS_PP,
          f"kernel and oracle fleet success differ by {d_succ} pp")
    check(d_p95 <= P95_REL,
          f"kernel and oracle P95 differ by {100 * d_p95}%")


def four_chips() -> None:
    devices = jax.devices()
    check(len(devices) == 4, f"--four-chips needs 4 devices, "
          f"found {len(devices)}")
    emit("four_chips", note="the sharded mega window is not ported to the "
         "Pallas kernel: every run here is use_pallas=False (XLA oracle)")
    big = api.run(api.Experiment(router="aif", mega=True, shard="auto",
                                 n_cells=8192, n_windows=600))
    peaks = [peak_bytes(d) for d in devices]
    emit("sharded_memory", n_cells=big.experiment.n_cells,
         n_windows=big.experiment.n_windows,
         cells_per_device=big.cells_per_device, wall_s=big.wall_s,
         success_pct=big.success_pct, p95_ms=big.p95_ms,
         peak_bytes_in_use=peaks)
    check(max(peaks) <= PEAK_SPREAD * min(peaks),
          f"per-device peak memory is uneven: {peaks}")
    base = dict(router="aif", mega=True, n_cells=2048, n_windows=600)
    sharded = api.run(api.Experiment(**base, shard="auto"))
    sharded_warm = api.run(api.Experiment(**base, shard="auto"))
    single = api.run(api.Experiment(**base))
    diffs = {
        "success_pct": abs(sharded.success_pct - single.success_pct),
        "obs_frac": abs(sharded.obs_frac - single.obs_frac),
        "tier_share": float(np.max(np.abs(sharded.tier_share
                                          - single.tier_share))),
        "routed_share": float(np.max(np.abs(sharded.routed_share
                                            - single.routed_share))),
    }
    emit("sharded_vs_single", n_cells=single.experiment.n_cells,
         n_windows=single.experiment.n_windows,
         sharded_first_s=sharded.wall_s, sharded_warm_s=sharded_warm.wall_s,
         single_first_s=single.wall_s,
         sharded_success_pct=sharded.success_pct,
         single_success_pct=single.success_pct,
         sharded_p95_ms=sharded.p95_ms, single_p95_ms=single.p95_ms,
         bound=SHARD_ATOL, **diffs)
    for name, d in diffs.items():
        check(d < SHARD_ATOL, f"sharded and unsharded {name} differ by {d}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded mega phase on 4 chips")
    args = ap.parse_args(argv)

    device = jax.devices()[0]
    if device.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {device.platform!r}",
              file=sys.stderr)
        return 1
    emit("setup", compile_cache=enable_compile_cache(),
         platform=device.platform, kind=device.device_kind,
         count=len(jax.devices()))
    if args.four_chips:
        four_chips()
    else:
        precision_probe()
        small_parity()
        deployment(device)
    print(json.dumps({"ok": True, "device": {
        "platform": device.platform, "kind": device.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
