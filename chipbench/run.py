"""Run one benchmark cell once and print its result line.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up: the persistent compilation cache, a check that JAX sees a TPU whose
``device_kind`` is in ``chipbench/peaks.json`` and as many chips as the cell
asks for, the cell's experiment from its files, a bit-for-bit check of the
program's world against the benchmark's own generator, and one warm-up call.
Window: a closed loop with one caller — ``repro.api.run(experiment)`` back
to back until ``--seconds`` have passed, the last call let finish.  Then the
peak device memory is read, the program's state is freed and the calls'
results are compared with the plain reference (``chipbench/check.py``).

With ``--trace 0`` the result line carries the cell's end-to-end metrics;
with ``--trace 1`` the window runs under the profiler and the line carries
the per-layer metrics reduced from its trace.  ``--rehearse`` runs a tiny
fleet on any backend, prints the compared numbers and no result line.
"""
import time

T_START = time.monotonic()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from chipbench import registry  # noqa: E402

SPAN = "chipbench."
#: fleet size and horizon of a ``--rehearse`` run
REHEARSE_CELLS, REHEARSE_WINDOWS = 16, 40


def fail(msg: str, code: int = 2):
    print(f"chipbench: {msg}", file=sys.stderr, flush=True)
    sys.exit(code)


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny fleet on any backend; prints no result line")
    ap.add_argument("--keep-trace", metavar="DIR",
                    help="copy the traced window's .xplane.pb into DIR")
    return ap.parse_args(argv)


def device_check(jax, chips: int, rehearse: bool):
    devs = jax.devices()
    if rehearse:
        return devs, None
    if devs[0].platform != "tpu":
        fail(f"needs a TPU; JAX found {devs[0].platform!r}")
    table = registry.peaks()["devices"]
    kind = devs[0].device_kind
    if kind not in table:
        fail(f"device kind {kind!r} is not in chipbench/peaks.json")
    if len(devs) < chips:
        fail(f"the cell needs {chips} chips; JAX found {len(devs)}")
    return devs, table[kind]


def keep_trace(trace_dir: str, dest: str) -> None:
    from chipbench import trace as trace_mod
    out = Path(dest)
    out.mkdir(parents=True, exist_ok=True)
    shutil.copy(trace_mod.newest_xplane(trace_dir), out / "window.xplane.pb")


def main(argv=None) -> int:
    args = parse(argv)
    try:
        registry.workload(args.workload)
        cell = registry.cell(args.workload)
    except (KeyError, FileNotFoundError) as e:
        fail(f"unknown workload {args.workload!r}: {e}")

    import jax

    from repro import api
    from repro.api import experiment as experiment_mod
    from repro.compile_cache import enable_compile_cache

    from chipbench import check, world

    if not args.rehearse:
        enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devs, peak = device_check(jax, int(cell["chips"]), args.rehearse)

    size = ((REHEARSE_CELLS, REHEARSE_WINDOWS) if args.rehearse else
            (cell["config"]["n_cells"], cell["traffic"]["n_windows"]))
    e = api.Experiment(**world.experiment_kwargs(cell, args.seed, *size))

    # the program's world for this experiment, checked against our own
    _, _, env_step = experiment_mod._build_world(
        e.resolve_topology(), e.scenario, e.n_cells, e.n_windows, e.window_s,
        e.seed, e.resolve_graph())
    bad = world.guard(cell, env_step.fluid, e.n_cells, e.n_windows)
    if bad:
        fail("the program's world differs from the benchmark's traffic: "
             + ", ".join(bad))

    warm = api.run(e)
    reference_digest = check.digest(warm)
    del warm
    gc.collect()
    setup_s = time.monotonic() - T_START

    trace_dir = None
    if args.trace:
        trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-")
        jax.profiler.start_trace(trace_dir)
    calls, differing, res = 0, 0, None
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation(SPAN + "window"):
        while True:
            res = None
            with jax.profiler.TraceAnnotation(SPAN + "api_run"):
                res = api.run(e)
            calls += 1
            differing += int(check.digest(res) != reference_digest)
            if time.perf_counter() - t0 >= args.seconds:
                break
    elapsed = time.perf_counter() - t0
    if args.trace:
        jax.profiler.stop_trace()
    peak_bytes = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                     for d in devs)

    rows = check.sample_rows(args.seed, e.n_cells, int(cell["sample_cells"]))
    data = check.gather(res, rows)
    del res
    gc.collect()
    compared = check.compare(cell, args.seed, data, differing)
    correct = all(v <= lim for _, v, lim in compared)

    def report():
        """The compared numbers beside their limits: the last lines on
        standard error."""
        for name, v, lim in compared:
            print(f"check {name} = {v!r} (limit {lim!r})", file=sys.stderr)
        print(f"correct = {correct}", file=sys.stderr, flush=True)

    if args.rehearse:
        report()
        return 0

    cw = e.n_cells * e.n_windows * calls
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": peak_bytes}
    line = {"correct": bool(correct), "attempted": calls,
            "failed": differing}
    if not args.trace:
        values = {"cell_windows_per_s": cw / elapsed,
                  "peak_hbm_gb": peak_bytes / 1e9, "setup_s": setup_s}
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in registry.metrics_for(args.workload, "end_to_end")}
        line.update(metrics=metrics, device=device)
    else:
        from chipbench import layers
        if args.keep_trace:
            keep_trace(trace_dir, args.keep_trace)
        ctx = layers.context(trace_dir, cell, e, calls, peak)
        metrics = {}
        for m in registry.metrics_for(args.workload, "per_layer"):
            v = registry.metric_reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device.update(busy_s=ctx.busy_s, window_s=ctx.window_s)
        shutil.rmtree(trace_dir, ignore_errors=True)
        line.update(metrics=metrics, device=device,
                    breakdown=layers.breakdown(ctx))
        for note in ctx.notes:
            print(f"note {note}", file=sys.stderr)
    print(f"calls {calls} elapsed_s {elapsed!r}", file=sys.stderr)
    report()
    line["checks"] = {name: {"value": v, "limit": lim}
                      for name, v, lim in compared}
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
