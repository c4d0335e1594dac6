"""Compile the AIF path's Pallas kernels for a described TPU v5e.

The TPU compiler is installed without a chip: it compiles for a chip that is
described and not attached, so these tests catch what interpret mode cannot
— block shapes off the (8, 128) tiling, ops Mosaic cannot lower, fast-memory
overflows and programs that do not fit one chip's HBM — at the paper's
widths (S=243, A=20) and at deployment fleet sizes.  Nothing runs; the
numbers are checked on the chip by ``chip_smoke.py``.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.layout import Format, Layout
from jax.sharding import SingleDeviceSharding

from repro.api import engine
from repro.api import experiment as experiment_mod
from repro.core import mega as mega_core
from repro.core import policies
from repro.core.topology import default_topology
from repro.envsim import batched
from repro.kernels.efe import mega as mega_kernel
from repro.kernels.efe import ops as efe_ops
from repro.kernels.efe.efe import belief_efe_fleet_pallas, efe_fleet_pallas

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


def _fleet_shapes(r, masked):
    topo_ = default_topology()
    s, a = topo_.n_states, policies.n_actions(topo_)
    m, nb = topo_.n_modalities, topo_.max_bins
    f32 = jnp.float32
    assert (s, a) == (243, 20)
    shapes = dict(b_norm=((r, a, s, s), f32), q=((r, s), f32),
                  a_norm=((r, m, nb, s), f32), logc=((r, m, nb), f32),
                  amb=((r, s), f32), cost=((a,), f32),
                  obs_mask=((r, m), f32) if masked else None)
    return s, shapes


@pytest.mark.parametrize("masked", [False, True], ids=["clean", "masked"])
@pytest.mark.parametrize("r", [64, 2048])
def test_efe_fleet_kernel_compiles(one_chip, r, masked):
    _, sh = _fleet_shapes(r, masked)
    args = {k: None if v is None else _sds(*v, one_chip)
            for k, v in sh.items()}
    compiled = efe_fleet_pallas.lower(
        args["b_norm"], args["q"], args["a_norm"], args["logc"],
        args["amb"], args["cost"], args["obs_mask"],
        interpret=False).compile()
    _assert_fits_one_chip(compiled)


@pytest.mark.parametrize("masked", [False, True], ids=["clean", "masked"])
@pytest.mark.parametrize("r", [64, 2048])
def test_belief_efe_kernel_compiles(one_chip, r, masked):
    s, sh = _fleet_shapes(r, masked)
    args = {k: None if v is None else _sds(*v, one_chip)
            for k, v in sh.items()}
    b_prev = _sds((r, s, s), jnp.float32, one_chip)
    compiled = belief_efe_fleet_pallas.lower(
        b_prev, args["q"], args["q"], args["b_norm"], args["a_norm"],
        args["logc"], args["amb"], args["cost"], args["obs_mask"],
        interpret=False).compile()
    _assert_fits_one_chip(compiled)


def _mega_world(r, t, scenario):
    topo_ = default_topology()
    scfg, params, env_step = experiment_mod._build_world(
        topo_, scenario, r, t, 1.0, 0)
    router = experiment_mod._make_aif(topo_, scfg, False, True, True)
    return router, params, env_step


@pytest.mark.parametrize("r,t,scenario", [
    (64, 120, "paper-burst"), (64, 120, "flaky-telemetry"),
    (2048, 600, "paper-burst")])
def test_mega_window_compiles(one_chip, r, t, scenario):
    """One whole-window megakernel launch at horizon T (a T-slot tape)."""
    router, params, env_step = _mega_world(r, t, scenario)
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


def test_mega_rollout_fits_one_chip(one_chip, monkeypatch):
    """The whole R=2048 x T=600 mega rollout program with the compiled
    kernel fits one chip's HBM.  Its inputs keep the device's default
    layouts, as arrays created outside the program do; the engine's backend
    probe sees the CPU here, so the test selects the compiled kernel."""
    monkeypatch.setattr(efe_ops, "_auto_interpret", lambda: False)
    r, t = 2048, 600
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
    compiled = engine._mega_impl.lower(
        *args, None, None, None, None, key, t0, router=router, n_steps=t,
        obs_masked=bool(env_step.emits_mask), dt=fl.dt,
        scrape_every=fl.scrape_every,
        restart_blackout=fl.restart_blackout).compile()
    _assert_fits_one_chip(compiled)
