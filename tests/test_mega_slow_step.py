"""The mega engine's slow boundary: the replayed batch folded as a slot-hit
histogram against the gathered batch.  (The streaming cache advance is
checked on run-warm states in ``test_mega_parity.py``, which compiles the
same rollouts.)"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import generative
from repro.core import mega as mega_core
from repro.core.topology import default_topology, five_tier_topology

from mega_helpers import TWO_TIER


# ------------------------------------------------ replayed slot-hit histogram
def _gathered_slow_step(state, k_slow, cfg):
    """The slow boundary in its earlier form: the replayed batch gathered
    draw by draw, the A update and the colsum delta summed over draws, and
    ``wcount`` bumped by a scatter-add.  Returns (a_counts, wcount, colsum).
    """
    topo, slots = cfg.topology, state.slots
    r, j = slots.action.shape
    batch = cfg.replay_batch
    size = jnp.minimum(state.t, j)
    idx = jax.vmap(lambda k, n: jax.random.randint(
        k, (batch,), 0, jnp.maximum(n, 1)))(k_slow, size)
    valid = (size > 0).astype(jnp.float32)[:, None] * jnp.ones((1, batch))

    def take(arr):
        ix = idx if arr.ndim == 2 else idx[..., None]
        return jnp.take_along_axis(arr, ix, axis=1)

    qp_b = take(slots.q_prev.astype(jnp.float32))
    qn_b = take(slots.q_next.astype(jnp.float32))
    onehot = (take(slots.obs_bins)[..., None] == jnp.arange(topo.max_bins))
    wgt = onehot * valid[..., None, None] * take(slots.obs_mask)[..., None]
    a_counts = state.a_counts + cfg.alpha_a * mega_core._einsum(
        "rnmb,rns->rmbs", wgt.astype(jnp.float32), qn_b)
    wcount = slots.wcount.at[jnp.arange(r)[:, None], idx].add(valid)
    w = jax.nn.sigmoid((take(slots.dt_since_change) - cfg.settle_midpoint_s)
                       / cfg.settle_scale_s) * valid
    oh = jax.nn.one_hot(take(slots.action), cfg.n_actions) * w[..., None]
    d_col = cfg.alpha_b * mega_core._einsum(
        "rna,rns->ras", oh * jnp.sum(qn_b, -1)[..., None], qp_b)
    return a_counts, wcount, state.cache.colsum + d_col


def _filled_state(cfg, r, j, t, *, masked=False, slot_dtype=jnp.float32,
                  seed=0):
    """A fleet state whose first ``min(t, j)`` slots hold random
    transitions, with earlier slot hits and a learnt A already in place."""
    topo = cfg.topology
    s, m = topo.n_states, topo.n_modalities
    ks = jax.random.split(jax.random.key(seed), 8)
    st = mega_core.init_mega_state(cfg, r, j, slot_dtype=slot_dtype)
    filled = (jnp.arange(j) < min(t, j))[None, :]

    def dist(k):
        q = jax.random.dirichlet(k, jnp.full((s,), 0.3), (r, j))
        return (q * filled[..., None]).astype(slot_dtype)

    obs_bins = jnp.stack([jax.random.randint(jax.random.fold_in(ks[2], i),
                                             (r, j), 0, nb)
                          for i, nb in enumerate(topo.n_bins)], axis=-1)
    obs_mask = (jax.random.bernoulli(ks[3], 0.7, (r, j, m)).astype(
        jnp.float32) if masked else jnp.ones((r, j, m), jnp.float32))
    slots = st.slots._replace(
        q_prev=dist(ks[0]), q_next=dist(ks[1]), obs_bins=obs_bins,
        obs_mask=obs_mask,
        action=jax.random.randint(ks[4], (r, j), 0, cfg.n_actions),
        dt_since_change=jax.random.uniform(ks[5], (r, j), maxval=60.0),
        wcount=jax.random.randint(ks[6], (r, j), 0, 4).astype(jnp.float32)
        * filled)
    a_counts = st.a_counts * jax.random.uniform(ks[7], st.a_counts.shape,
                                                minval=1.0, maxval=3.0)
    return st._replace(a_counts=a_counts, slots=slots,
                       cache=mega_core._refresh_cache(a_counts, slots, cfg),
                       t=jnp.full((r,), t, jnp.int32))


@pytest.mark.parametrize("topology,r,j,t,batch,kw", [
    (None, 6, 40, 25, 100, {}),
    (None, 6, 40, 0, 100, {}),
    (None, 6, 40, 40, 100, dict(masked=True)),
    (TWO_TIER, 4, 30, 30, 100, {}),
    (five_tier_topology(), 4, 30, 20, 100, {}),
    (None, 5, 23, 23, 100, {}),
    (None, 6, 40, 40, 100, dict(slot_dtype=jnp.bfloat16)),
    (None, 6, 60, 60, 3, dict(masked=True)),
    (None, 6, 61, 50, 3, dict(masked=True)),
    (None, 6, 61, 61, 3, dict(slot_dtype=jnp.bfloat16)),
], ids=["repeated-draws", "t0", "masked", "k2", "k5", "odd-r", "bf16-slots",
        "small-batch-masked", "long-tape", "long-tape-bf16-slots"])
def test_replay_histogram_matches_gathered_batch(topology, r, j, t, batch, kw):
    """Folding the replayed batch as its slot-hit histogram is the gathered
    batch summed over slots: the same draws bump ``wcount`` bit for bit, and
    the A update and the colsum delta agree up to float32 association, on
    tapes short and long against the batch.  The step gathers nothing."""
    cfg = generative.AifConfig(topology=topology or default_topology(),
                               replay_batch=batch)
    state = _filled_state(cfg, r, j, t, **kw)
    ks = jax.random.split(jax.random.key(7), r)
    a_ref, wcount_ref, colsum_ref = _gathered_slow_step(state, ks, cfg)
    step = functools.partial(mega_core.mega_slow_step, cfg=cfg)
    assert "gather" not in str(jax.make_jaxpr(step)(state, ks))
    new = step(state, ks)
    np.testing.assert_array_equal(np.asarray(new.slots.wcount),
                                  np.asarray(wcount_ref))
    drawn = np.asarray(new.slots.wcount - state.slots.wcount)
    assert drawn.sum() == (r * batch if t else 0)
    if t and batch > t:
        assert drawn.max() > 1
    np.testing.assert_allclose(np.asarray(new.a_counts),
                               np.asarray(a_ref), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(new.cache.colsum),
                               np.asarray(colsum_ref), rtol=1e-6)
