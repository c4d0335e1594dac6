"""Readings the limits of ``correct`` are set from, at a cell's own size.

    python chipbench/control.py --workload <cell> --seeds 1,2,3 \
        [--kinds program,reference_low,...] [--rehearse]

For every seed, the compared numbers (``chipbench/check.py``) of runs of the
cell's experiment over the same sampled cells, one per kind asked for (all
by default):

* ``program`` — the program as the configuration states it (float32 slots);
  what a benchmark run compares, the lower readings;
* ``reference_low`` — the reference one precision step below the
  configuration's put in the program's place: contractions as three
  bfloat16 passes, the environment and the fleet reduction in bfloat16;
* ``bf16_slots`` — the program's own lower-precision path switched on
  (``mega_slot_dtype="bfloat16"``: the transition slots, the bulk of the
  mega engine's bytes, stored in bfloat16);
* the faults of :data:`PLANTS`, each planted under the timed path:
  ``action_fault`` (every cell's applied action changed at the last tick of
  every window, where the window produces it), ``slow_step_unchanged`` (the
  slow boundary returns its state unchanged) and ``half_replay_batch``
  (half of each replayed batch left out of the learning update).

One JSON line per seed.  The benchmark's own runs never run this.
``--rehearse`` runs a tiny fleet on any backend.
"""
import argparse
import contextlib
import dataclasses
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]


@contextlib.contextmanager
def patched(module, name: str, make):
    """``module.name`` replaced by ``make(original)`` for the block."""
    orig = getattr(module, name)
    setattr(module, name, make(orig))
    try:
        yield
    finally:
        setattr(module, name, orig)


def action_fault():
    """Every cell's action at a window's last tick changed to ``(a + 1) % A``
    where the window produces it."""
    from repro.kernels.efe import ops

    def make(orig):
        def window(*args, **kw):
            state, est, obs, ys = orig(*args, **kw)
            act = ys[0]
            act = act.at[-1].set((act[-1] + 1) % kw["cfg"].n_actions)
            return state, est, obs, (act,) + tuple(ys[1:])
        return window
    return patched(ops, "mega_window", make)


def slow_step_unchanged():
    """A slow boundary that returns its state unchanged (nothing learnt)."""
    from repro.core import mega
    return patched(mega, "mega_slow_step",
                    lambda orig: lambda state, k_slow, cfg, **kw: state)


def half_replay_batch():
    """Half of each replayed batch left out of the learning update."""
    from repro.core import mega

    def make(orig):
        def half(state, k_slow, cfg, **kw):
            return orig(state, k_slow, dataclasses.replace(
                cfg, replay_batch=cfg.replay_batch // 2), **kw)
        return half
    return patched(mega, "mega_slow_step", make)


#: faults planted in the program: name -> context manager factory
PLANTS = {"action_fault": action_fault,
          "slow_step_unchanged": slow_step_unchanged,
          "half_replay_batch": half_replay_batch}
KINDS = ("program", "reference_low", "bf16_slots") + tuple(PLANTS)


def _readings(api, check, e, rows):
    t0 = time.perf_counter()
    res = api.run(e)
    run_s = time.perf_counter() - t0
    data = check.gather(res, rows)
    del res
    gc.collect()
    return run_s, data


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated seeds")
    ap.add_argument("--kinds", default=",".join(KINDS),
                    help="comma-separated readings to take")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    kinds = args.kinds.split(",")
    if set(kinds) - set(KINDS):
        ap.error(f"unknown kinds {sorted(set(kinds) - set(KINDS))}")

    import jax

    from repro import api
    from repro.compile_cache import enable_compile_cache

    from chipbench import check, reference, registry, world

    if not args.rehearse:
        enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        if jax.devices()[0].platform != "tpu":
            print("control: needs a TPU (or --rehearse)", file=sys.stderr)
            return 2
    cell = registry.cell(args.workload)
    model = reference.Model(cell["config"])
    size = (16, 40) if args.rehearse else (None, None)
    for seed in (int(s) for s in args.seeds.split(",")):
        e = api.Experiment(**world.experiment_kwargs(cell, seed, *size))
        rows = check.sample_rows(seed, e.n_cells, int(cell["sample_cells"]))
        out = {"workload": args.workload, "seed": seed}
        if {"program", "reference_low"} & set(kinds):
            run_s, data = _readings(api, check, e, rows)
            t0 = time.perf_counter()
            out["program"] = check.program_numbers(cell, model, seed, rows,
                                                   data)
            out["run_s"], out["reference_s"] = run_s, time.perf_counter() - t0
            if "reference_low" in kinds:
                out["reference_low"] = check.reference_low_numbers(
                    cell, model, seed, rows, data)
            del data
        if "bf16_slots" in kinds:
            e16 = dataclasses.replace(e, mega_slot_dtype="bfloat16")
            _, data = _readings(api, check, e16, rows)
            out["bf16_slots"] = check.program_numbers(cell, model, seed, rows,
                                                      data)
            del data
        for name in (k for k in KINDS if k in PLANTS and k in kinds):
            with PLANTS[name]():
                jax.clear_caches()
                try:
                    _, data = _readings(api, check, e, rows)
                finally:
                    jax.clear_caches()
            out[name] = check.program_numbers(cell, model, seed, rows, data)
            del data
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
