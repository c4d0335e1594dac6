"""Compile the AIF path's Pallas kernels for a described TPU v5e.

The TPU compiler is installed without a chip: it compiles for a chip that is
described and not attached, so these tests catch what interpret mode cannot
— block shapes off the (8, 128) tiling, ops Mosaic cannot lower, fast-memory
overflows and programs that do not fit one chip's HBM — at the paper's
widths (S=243, A=20) and at deployment fleet sizes.  Nothing runs; the
numbers are checked on the chip by ``chip_smoke.py``.
"""
import functools
import re

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.layout import Format, Layout
from jax.sharding import SingleDeviceSharding

from repro.api import engine
from repro.api import experiment as experiment_mod
from repro.core import mega as mega_core
from repro.core.topology import get_topology
from repro.envsim import batched
from repro.kernels.efe import mega as mega_kernel
from repro.kernels.efe import ops as efe_ops

HBM_BYTES = 16e9          # one TPU v5e chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described chip, with the persistent compilation cache off (a
    described-chip compile can be written to it but never read back)."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _sds(shape, dtype, sharding, row_major=True):
    """Abstract operand on the described chip.  A kernel's operands are
    row-major, as a producer inside the engine's program writes them; an
    entry parameter left at the device's default layout may be transposed
    (f32[R, 243, ...] puts R on the lanes), and the kernel's row-major
    operand constraint then costs a full copy."""
    if not row_major or jax.dtypes.issubdtype(dtype, jax.dtypes.extended):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
    fmt = Format(Layout(tuple(range(len(shape)))), sharding)
    return jax.ShapeDtypeStruct(shape, dtype, sharding=fmt)


def _abstract(tree, sharding, row_major=True):
    return jax.tree_util.tree_map(
        lambda x: _sds(x.shape, x.dtype, sharding, row_major), tree)


def _assert_fits_one_chip(compiled):
    assert "tpu_custom_call" in compiled.as_text()
    ma = compiled.memory_analysis()
    used = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    assert used < HBM_BYTES, f"{used / 1e9:.2f} GB does not fit one chip"


def _mega_world(r, t, scenario, topology="paper-3tier"):
    topo_ = get_topology(topology)
    scfg, params, env_step = experiment_mod._build_world(
        topo_, scenario, r, t, 1.0, 0)
    router = experiment_mod._make_aif(topo_, scfg, True, True)
    return router, params, env_step


_WINDOWS = [
    (64, 120, "paper-burst", "paper-3tier"),
    (64, 120, "flaky-telemetry", "paper-3tier"),
    (2048, 600, "paper-burst", "paper-3tier"),
    (2048, 600, "flaky-telemetry", "paper-3tier"),
    (8, 3600, "paper-burst", "paper-3tier"),
    (256, 3600, "paper-burst", "paper-3tier"),
    (64, 120, "paper-burst", "continuum-5tier")]


@pytest.mark.parametrize(
    "r,t,scenario,topology", _WINDOWS,
    ids=[f"{r}-{t}-{s}" + ("" if k == "paper-3tier" else f"-{k}")
         for r, t, s, k in _WINDOWS])
def test_mega_window_compiles(one_chip, r, t, scenario, topology):
    """One whole-window megakernel launch at horizon T (a T-slot tape; at
    T=3600 past what VMEM holds, so a resident prefix and a streamed rest),
    with clean and masked telemetry, at K=3 and at the K=5 continuum."""
    router, params, env_step = _mega_world(r, t, scenario, topology)
    cfg, fl = router.cfg, env_step.fluid
    w, m, k = router.period, router.n_modalities, router.n_tiers
    f32 = jnp.float32
    state = jax.eval_shape(lambda: mega_core.init_mega_state(cfg, r, t))
    est = jax.eval_shape(lambda: batched.init_fluid_state(params))
    obs = tuple(jax.ShapeDtypeStruct(s, f32)
                for s in ((r, m), (r, k), (r, k), (r, k), (r, m)))
    keys = jax.eval_shape(lambda: jax.random.split(jax.random.key(0), w))
    ov = (None if fl.obs_valid is None
          else jax.ShapeDtypeStruct((w, r, m), f32))
    args = _abstract((state, est, obs, params,
                      jax.ShapeDtypeStruct((w, r), f32),
                      jax.ShapeDtypeStruct((w, r, k), f32), ov, keys,
                      jax.ShapeDtypeStruct((w, r, cfg.n_actions), f32),
                      jax.ShapeDtypeStruct((), jnp.int32)), one_chip)
    window = functools.partial(
        mega_kernel.mega_window_pallas, cfg=cfg, disc=router.resolved_disc,
        util_edges=router.resolved_util_edges,
        util_period=router.util_period, dt=fl.dt,
        scrape_every=fl.scrape_every, restart_blackout=fl.restart_blackout,
        emits_mask=bool(env_step.emits_mask), interpret=False)
    _assert_fits_one_chip(jax.jit(window).lower(*args).compile())


def _compile_mega_rollout(one_chip, r, t):
    """The whole R x T mega rollout program with the compiled kernel,
    compiled for one chip.  Its inputs keep the device's default layouts,
    as arrays created outside the program do; the engine's backend probe
    sees the CPU here, so the compiled kernel is selected by hand."""
    router, params, env_step = _mega_world(r, t, "paper-burst")
    fl = env_step.fluid
    m, k = router.n_modalities, router.n_tiers
    f32 = jnp.float32
    state = jax.eval_shape(
        lambda: mega_core.init_mega_state(router.cfg, r, t))
    est = jax.eval_shape(lambda: batched.init_fluid_state(params))
    obs = tuple(jax.ShapeDtypeStruct(s, f32)
                for s in ((r, m), (r, k), (r, k), (r, k), (r, m)))
    args = _abstract((state, est, obs, fl.params, fl.arrival_rate,
                      fl.hazard_scale), one_chip, row_major=False)
    key = _abstract(jax.eval_shape(lambda: jax.random.key(0)), one_chip)
    t0 = _sds((), jnp.int32, one_chip, row_major=False)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(efe_ops, "_auto_interpret", lambda: False)
        return engine._mega_impl.lower(
            *args, None, None, None, None, key, t0, router=router,
            n_steps=t, obs_masked=bool(env_step.emits_mask), dt=fl.dt,
            scrape_every=fl.scrape_every,
            restart_blackout=fl.restart_blackout).compile()


@pytest.fixture(scope="module")
def mega_rollout(one_chip):
    """The R=2048 x T=600 mega rollout program, compiled for one chip."""
    return _compile_mega_rollout(one_chip, 2048, 600)


def test_mega_rollout_fits_one_chip(mega_rollout):
    """The whole mega rollout program fits one chip's HBM."""
    _assert_fits_one_chip(mega_rollout)


@pytest.fixture(scope="module")
def hour_long_rollout(one_chip):
    """The R=256 x T=3600 mega rollout program (one hour of 1 s windows, a
    tape past what VMEM holds), compiled for one chip."""
    return _compile_mega_rollout(one_chip, 256, 3600)


def test_hour_long_mega_rollout_fits_one_chip(hour_long_rollout):
    """The hour-long rollout compiles with the kernel and fits one chip's
    HBM."""
    _assert_fits_one_chip(hour_long_rollout)


_CALLEES = re.compile(
    r"\b(?:calls|to_apply|body|condition)=(\{[^}]*\}|%?[\w.\-]+)")


def _loop_tape_copies(compiled, r, j):
    """The ``copy`` ops of a whole slot tape, f32[r, j, 243], that run on
    every window: those in the body of the scan that launches the kernel
    and in what it calls, leaving out the branches of a ``conditional``
    (the watchdog's quarantine, taken only when a cell diverges).  The
    copies at the program's entry and exit run once a call."""
    comps, name = {}, None
    for line in compiled.as_text().splitlines():
        head = re.match(r"(?:ENTRY )?%?([\w.\-]+) \(.*\{\s*$", line)
        if head:
            name = head.group(1)
            comps[name] = []
        elif name is not None:
            comps[name].append(line)
    bodies = [re.search(r"\bbody=%?([\w.\-]+)", ln).group(1)
              for lines in comps.values() for ln in lines if " while(" in ln]
    todo = [b for b in bodies
            if any("tpu_custom_call" in ln for ln in comps[b])]
    assert len(todo) == 1, bodies
    seen = set()
    while todo:
        c = todo.pop()
        if c not in seen:
            seen.add(c)
            todo += [n.lstrip("%") for ln in comps[c]
                     for m in _CALLEES.finditer(ln)
                     for n in re.findall(r"%?[\w.\-]+", m.group(1))]
    tape = re.compile(rf"%\S+ = f32\[{r},{j},243\]\{{[^}}]*\}} copy\(")
    return [m.group(0) for c in seen for ln in comps[c]
            for m in [tape.search(ln)] if m]


@pytest.mark.parametrize("rollout,r,t", [
    ("mega_rollout", 2048, 600), ("hour_long_rollout", 256, 3600)],
    ids=["2048-600", "256-3600"])
def test_mega_rollout_loop_copies_no_slot_tape(request, rollout, r, t):
    """The rollout's window loop keeps the ``q_prev``/``q_next`` tapes
    row-major, as the megakernel reads them, so no window copies a whole
    tape; at T=3600 XLA would otherwise lay the carry out slot-minor and
    turn both tapes around every window."""
    copies = _loop_tape_copies(request.getfixturevalue(rollout), r, t)
    assert not copies, copies


def test_mega_rollout_names_its_kernel_and_scopes(mega_rollout):
    """The compiled program keeps the megakernel's name and the scopes a
    profiler trace attributes its ops by."""
    text = mega_rollout.as_text()
    assert "aif_mega_window" in text
    for scope in ("aif.window", "aif.window.draw", "aif.window.land",
                  "aif.slow_step", "aif.watchdog"):
        assert f"/{scope}/" in text, scope


def test_mega_slow_step_folds_replay_without_gathers(mega_rollout):
    """The slow boundary folds its replayed batch as a slot-hit histogram:
    no gather op sits under its scope, the histogram and its contractions
    sit under ``aif.slow_step.replay``, and the program still fits."""
    lines = mega_rollout.as_text().splitlines()
    slow = [ln for ln in lines if "/aif.slow_step/" in ln]
    assert slow
    gathers = [ln for ln in slow if " gather(" in ln]
    assert not gathers, gathers[:3]
    assert any("/aif.slow_step.replay/" in ln for ln in slow)
    _assert_fits_one_chip(mega_rollout)
