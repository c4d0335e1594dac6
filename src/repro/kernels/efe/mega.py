"""Whole-window Pallas megakernel: W fused fast ticks per launch.

One launch advances a router block through an entire slow period — belief
update (Eq. 2) -> factored EFE (Eq. 1) -> in-kernel categorical sampling
(argmax over pre-drawn Gumbel noise) -> dwell gate -> adaptive-preference
error EMA -> fluid env window — with every carried tensor resident in VMEM
for all W ticks: the (BR, J, S) transition slots, the factored
:class:`repro.core.mega.MegaCache` tensors, the posterior, and the whole
per-cell env state.  Nothing round-trips to HBM between ticks; HBM traffic
is one read of the quasi-static operands and one write of the window's
posteriors and trace per window instead of per tick.

The XLA oracle twin is :func:`repro.core.mega.mega_window` (same op order,
same guard constants); parity is pinned at 1e-4 window by window by
``tests/test_mega_window_k{2,3,5}.py`` and rollout by rollout by
``tests/test_mega_kernel.py``, ``test_mega_tape.py`` and
``test_mega_launch.py``.  Known intentional deviations, both inside that
tolerance:

* the env's completion-weighted P95 replaces the oracle's
  ``argsort``/``cumsum`` with a sort-free O(K²) crossing test (TPU has no
  cheap in-kernel sort; the selected atom is identical, only the cumulative
  mass summation order differs), and
* the EFE contractions over the state and slot axes run on the MXU at
  ``Precision.HIGHEST`` (float32 passes) instead of ``einsum``
  (floating-point reassociation only).  One-hot selections — the applied
  action's transition column sums and slot coefficients, the routing-weight
  row — are VPU multiply-and-sum reductions, exact in any precision mode.

PRNG contract: the kernel draws nothing.  The caller pre-splits the legacy
per-tick key chain into a per-window block — ``gumbel`` (W, R, A) for the
policy categorical (``argmax(log p + gumbel)`` is bitwise
``jax.random.categorical``) and ``uniforms`` (W, 2, R, K) for the env
restart fire/duration draws — so randomness is bit-identical to the
per-tick engine at any window size.

Transition slots: the kernel reads the (BR, J, S) slot tape and never
writes it.  Slots pushed inside the window carry zero coefficients until
the next slow boundary re-weighs them (exactly what the oracle relies on),
so the kernel emits only the window's W posteriors and the wrapper lands
the window's slot block with one contiguous write, as the oracle does.
Slots may be stored bfloat16 (``MegaSlots`` dtype); all accumulation is
float32.

TPU layout: the cell axis sits on the sublane dim of every block — router
blocks are 8 rows and R is padded to a multiple of 8 with copies of the
last router (every op is row-local; padded rows are cropped) — per-window
schedules and traces are (W, ..., R, trailing) with the small axes leading,
and per-router scalars are (BR, 1) columns.  The state axis keeps its true
width S (243 for the paper topology; Mosaic masks the partial lane tile),
so the slot tape is read in place, never padded.  The kernel reads the
``q_prev``/``q_next`` tapes row-major; the rollout loop carries them so
because :func:`repro.core.mega.row_major_tapes` states that layout.  Without
it XLA keeps a long tape (J = 3,600) slot-minor in the loop's carry and
copies both tapes row-major for the kernel every window.

Slot tape and horizon: the tape (``q_prev``, ``q_next``, ``qnproj|sumqn``
and ``coefact``, J slots each) is folded in :data:`SLOT_CHUNK`-slot chunks.
:func:`tape_plan` prices a launch from shapes alone: where the 8-router
block's whole tape fits :data:`VMEM_LIMIT` it is held resident (read once a
window); where it does not, the launch holds the longest resident prefix
the priced VMEM leaves room for and streams the rest from HBM, one chunk at
a time into two VMEM buffers, so the copy of the next chunk overlaps the
fold of this one.  Resident or streamed, the fold reads only the chunks
that hold a filled slot (slot index < t0, :func:`live_chunks`: the later
slots carry zero coefficients and add nothing to it), and a streamed
chunk is copied at every tick whose fold needs it.  The arithmetic and its
order within a chunk are the same either way.  The
launch asks the compiler for exactly the priced scoped VMEM and refuses one
whose non-tape blocks alone exceed the ceiling; the horizon's one bound is
the slots' replay capacity (:func:`repro.core.mega.init_mega_state`).

The kernel compiles for a TPU v5e (``tests/test_chip_compile.py`` compiles
it for a described chip at the paper's widths) and runs there compiled
(``chip_smoke.py``); on the CPU it runs in interpret mode.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import policies, preferences, spaces
from repro.core import mega as mega_core
from repro.envsim import batched

_EPS = 1e-9             # envsim.batched._EPS (restated: kernels stay leaf)
_HI = jax.lax.Precision.HIGHEST

_LANES = 128         # TPU lane width (VMEM tiles pad the minor dim to it)
SUBLANES = 8         # TPU sublane count: router blocks are multiples of this

#: Per-core VMEM of a TPU v5e (and v6e) TensorCore; the scoped limit a
#: kernel may request is capped below it.
VMEM_CAPACITY = 128 * 1024 * 1024

# Scoped-VMEM ceiling requested from the compiler (headroom below the
# 128 MiB TensorCore VMEM for Mosaic's own internal scratch).
VMEM_LIMIT = VMEM_CAPACITY - 16 * 1024 * 1024

# Slots per step of the in-kernel fold over the tape, and per streamed copy.
SLOT_CHUNK = 128

# The slot tape's operands, in the order the launch passes them.
TAPE = ("qp", "qn", "qnproj", "coefact")
# The tape operands each fold reads: the belief prior's and the EFE's.
_PRIOR_TAPE = ("coefact", "qp", "qn")
_EFE_TAPE = ("qp", "coefact", "qnproj")


def pad_rows(r: int, block_r: int = SUBLANES) -> int:
    """R rounded up to a whole number of router blocks."""
    return -(-r // block_r) * block_r


def pad_cells(x: jnp.ndarray, r_pad: int, axis: int = 0) -> jnp.ndarray:
    """Pad the cell axis to ``r_pad`` rows by repeating the last router.

    Padded rows are finite copies of a real router (every op in the kernel
    is row-local), and the wrapper crops them from the results.
    """
    extra = r_pad - x.shape[axis]
    if extra == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, extra)
    return jnp.pad(x, widths, mode="edge")


def block_vmem_bytes(shape, dtype) -> int:
    """VMEM footprint of one block: the two minor dims padded to the
    (sublane, lane) tile of ``dtype``."""
    itemsize = jnp.dtype(dtype).itemsize
    sub = SUBLANES * 4 // itemsize
    shape = (1,) * max(0, 2 - len(shape)) + tuple(shape)
    *lead, s2, s1 = shape
    return (math.prod(lead) * (-(-s2 // sub) * sub)
            * (-(-s1 // _LANES) * _LANES) * itemsize)


class TapePlan(NamedTuple):
    """How one launch reads its J-slot tape (:func:`tape_plan`)."""

    j_res: int      # leading slots held resident in VMEM (J: the whole tape)
    j_chunk: int    # slots per fold step and per streamed copy
    vmem: int       # scoped VMEM the launch asks for, in bytes
    widths: tuple   # (trailing width, dtype) of each operand, in TAPE order


def _batched_dot(a, b, contract_a: int, contract_b: int):
    """3-D batched contraction over the leading router axis (MXU, f32)."""
    return jax.lax.dot_general(
        a, b, dimension_numbers=(((contract_a,), (contract_b,)), ((0,), (0,))),
        precision=_HI, preferred_element_type=jnp.float32)


def mega_vmem_bytes(blocks, chunk_bytes: int) -> int:
    """Scoped VMEM of a launch's pipelined blocks, from their ``(block
    shape, dtype)`` list: every block double-buffered by the pipeline, plus
    room for four f32 temporaries the size of one tape chunk."""
    return (2 * sum(block_vmem_bytes(s, d) for s, d in blocks)
            + 4 * chunk_bytes)


def tape_widths(cfg, slot_dtype) -> dict:
    """Trailing width and dtype of each tape operand."""
    topo = cfg.topology
    s = topo.n_states
    return {"qp": (s, jnp.dtype(slot_dtype)), "qn": (s, jnp.dtype(slot_dtype)),
            "qnproj": (mega_core.n_proj(topo) + 1, jnp.dtype(jnp.float32)),
            "coefact": (cfg.n_actions, jnp.dtype(jnp.float32))}


def _fixed_blocks(cfg, w_ticks: int, has_obs_valid: bool) -> list:
    """(block shape, dtype) of every pipelined block of a launch but the
    tape's: the cache, carries, schedules and noise, shared tables and
    results (all 4-byte types), in :func:`mega_window_pallas`' order."""
    topo = cfg.topology
    s, a, k = topo.n_states, cfg.n_actions, topo.n_tiers
    m, nb, p = topo.n_modalities, topo.max_bins, mega_core.n_proj(topo)
    w, br = w_ticks, SUBLANES
    shapes = [(br, a, s), (br, p, s), (br, p), (br, m, nb, s), (br, s),
              (br, 1), (br, 2), (3, br, m), (br, k), (8, br, k), (br, 9),
              (16, br, k), (w, br, 1), (w, br, k), (w, 2, br, k), (w, br, a),
              (k, s), (2, m * nb), (1, a), (a, k)]
    if has_obs_valid:
        shapes.append((w, br, m))
    shapes += [(w, br, s), (w, br, 1), (w, br, 6), (w, 8, br, k),
               (w, 3, br, m), (8, br, k), (br, 9)]
    return [(sh, jnp.float32) for sh in shapes]


@functools.lru_cache(maxsize=64)
def tape_plan(cfg, j: int, w_ticks: int, slot_dtype, has_obs_valid: bool,
              *, slot_chunk: int = SLOT_CHUNK,
              vmem_limit: int = VMEM_LIMIT) -> TapePlan:
    """Price a launch from shapes and choose how it reads the tape.

    The whole tape stays resident where it fits ``vmem_limit``.  Else the
    resident prefix is the most whole chunks the VMEM left after the other
    blocks and the two streaming buffers of each tape operand can hold
    (possibly none), and the rest is streamed.  Raises ``ValueError`` where
    the other blocks and the buffers alone exceed ``vmem_limit``.
    """
    br = SUBLANES
    jc = min(j, slot_chunk)
    widths = tape_widths(cfg, slot_dtype)
    fixed = mega_vmem_bytes(_fixed_blocks(cfg, w_ticks, has_obs_valid),
                            block_vmem_bytes((br, jc, cfg.topology.n_states),
                                             jnp.float32))

    def resident(n):           # the pipeline double-buffers each block
        return 2 * sum(block_vmem_bytes((br, n, wd), dt)
                       for wd, dt in widths.values())

    if fixed + resident(j) <= vmem_limit:
        return TapePlan(j, jc, fixed + resident(j), tuple(widths.values()))
    buffers = sum(block_vmem_bytes((2, br, jc, wd), dt)
                  for wd, dt in widths.values())
    room = vmem_limit - fixed - buffers
    if room < 0:
        raise ValueError(
            f"megakernel launch needs {(fixed + buffers) / 2**20:.1f} MiB of "
            f"VMEM for its non-tape blocks and tape buffers alone (limit "
            f"{vmem_limit / 2**20:.1f} MiB)")
    j_res = min(room // resident(jc), (j - 1) // jc) * jc
    return TapePlan(j_res, jc, fixed + buffers + resident(j_res),
                    tuple(widths.values()))


def live_chunks(t0, start: int, stop: int, j_chunk: int):
    """The chunks of tape slots ``[start, stop)``, folded ``j_chunk`` at a
    time with the last one possibly short, that hold a filled slot (slot
    index < ``t0``, a traced int32): how many of the whole chunks, from the
    first, and whether the short one does.  The later slots carry zero
    coefficients until the next slow boundary re-weighs them, so a fold
    that skips them adds the same.  :func:`_live_slots` is the host's
    count of the same rule."""
    n_full, tail = divmod(stop - start, j_chunk)
    whole = jnp.clip(jax.lax.div(t0 - start + j_chunk - 1, j_chunk), 0,
                     n_full)
    return whole, (t0 > stop - tail) if tail else False


def _live_slots(t0: int, start: int, stop: int, j_chunk: int) -> int:
    """Slots of the tape part ``[start, stop)`` a fold at window start
    ``t0`` reads: the whole chunks, and the short last one, that hold a
    filled slot (:func:`live_chunks`, on the host)."""
    n_full, tail = divmod(stop - start, j_chunk)
    live = min(max(-(-(t0 - start) // j_chunk), 0), n_full) * j_chunk
    if tail and t0 > stop - tail:
        live += tail
    return live


def tape_bytes(cfg, r: int, j: int, t0: int, w_ticks: int, slot_dtype,
               has_obs_valid: bool, **plan_kw) -> int:
    """Bytes of slot tape one launch at window start ``t0`` reads from HBM,
    counted from shapes as the kernel reads them: the resident prefix once
    per router block, each live streamed chunk of the prior's operands at
    every tick and of the EFE's at every selecting tick.  True widths and
    padded rows: the lane padding the copies also move does not count."""
    plan = tape_plan(cfg, j, w_ticks, slot_dtype, has_obs_valid, **plan_kw)
    per_slot = {n: wd * dt.itemsize for n, (wd, dt) in zip(TAPE, plan.widths)}
    dwell = max(int(cfg.action_dwell_s / cfg.fast_period_s), 1)
    selecting = len(range(0, w_ticks, dwell))
    live = _live_slots(t0, plan.j_res, j, plan.j_chunk)
    streamed = live * (w_ticks * sum(per_slot[n] for n in _PRIOR_TAPE)
                       + selecting * sum(per_slot[n] for n in _EFE_TAPE))
    return pad_rows(r, SUBLANES) * (plan.j_res * sum(per_slot.values())
                                    + streamed)


def folded_slots(cfg, r: int, j: int, t0: int, w_ticks: int, slot_dtype,
                 has_obs_valid: bool, **plan_kw) -> int:
    """Slot rows the belief prior's fold of one launch at window start
    ``t0`` covers, counted from shapes: the live slots of the resident
    prefix and of the streamed rest, at every tick, for every padded
    row."""
    plan = tape_plan(cfg, j, w_ticks, slot_dtype, has_obs_valid, **plan_kw)
    live = (_live_slots(t0, 0, plan.j_res, plan.j_chunk)
            + _live_slots(t0, plan.j_res, j, plan.j_chunk))
    return pad_rows(r, SUBLANES) * w_ticks * live


def mega_window_pallas(state, est, obs_carry, params,
                       arrival: jnp.ndarray, hazard: jnp.ndarray,
                       obs_valid: jnp.ndarray | None,
                       k_env: jnp.ndarray, gumbel: jnp.ndarray,
                       t0: jnp.ndarray, *,
                       cfg, disc, util_edges, util_period: int, dt: float,
                       scrape_every: int, restart_blackout: bool,
                       emits_mask: bool, interpret: bool,
                       slot_chunk: int = SLOT_CHUNK,
                       vmem_limit: int = VMEM_LIMIT):
    """Pallas dispatch of one whole window; signature/result match
    :func:`repro.core.mega.mega_window`.

    ``t0`` must sit on a dwell boundary (the engine only launches windows
    there) so the selecting/held tick structure is compiled statically.
    ``interpret`` is deliberately required — only the :mod:`..ops` wrapper
    auto-detects the backend.
    ``slot_chunk`` and ``vmem_limit`` set the fold's chunk and the VMEM
    ceiling :func:`tape_plan` prices the launch against.
    """
    topo = cfg.topology
    slots, cache = state.slots, state.cache
    r, j, s = slots.q_prev.shape
    m, nb, k_t = topo.n_modalities, topo.max_bins, topo.n_tiers
    mnb = m * nb
    a_n = cfg.n_actions
    p_n = mega_core.n_proj(topo)
    w_ticks = gumbel.shape[0]
    dwell = max(int(cfg.action_dwell_s / cfg.fast_period_s), 1)
    br = SUBLANES
    r_pad = pad_rows(r, br)
    slot_dtype = slots.q_prev.dtype
    # the slot tape is folded in J_c-slot chunks (code size independent of
    # the horizon), the last one possibly short: the first j_res slots
    # from resident VMEM blocks, the rest streamed from HBM
    plan = tape_plan(cfg, j, w_ticks, slot_dtype, obs_valid is not None,
                     slot_chunk=slot_chunk, vmem_limit=vmem_limit)
    j_res, j_chunk = plan.j_res, plan.j_chunk
    streamed = j_res < j
    tail_s = (j - j_res) % j_chunk
    widths = {n: wd for n, (wd, _) in zip(TAPE, plan.widths)}
    # compiled, a copy moves whole (8, 128) tiles of the tape's HBM layout:
    # every lane of the padded width, and the short last chunk's rows up to
    # a whole sublane tile (both inside the padded allocation); the
    # interpreter copies the exact window
    lanes = {n: wd if interpret else -(-wd // 128) * 128
             for n, wd in widths.items()}
    tail_rows = tail_s if interpret else -(-tail_s // 8) * 8

    # ---- static closure constants (inlined into the kernel) ---------------
    edges_list = [np.asarray(e, np.float32) for e in disc.modality_edges()]
    uedges = np.asarray(util_edges, np.float32)
    sf_tbl = np.ascontiguousarray(
        np.asarray(spaces.state_factor_table(topo))[:, 2:2 + k_t].T)
    eps_u = 0.15                      # belief.util_log_likelihood default
    # evaluate the shared jnp-valued model constants eagerly (the wrapper is
    # usually traced under the engine's jit — these must be embeddable)
    with jax.ensure_compile_time_eval():
        logc_nom_j, logc_uns_j = preferences.preference_log_tables(cfg)
        logc_tbl = np.stack([np.asarray(logc_nom_j).reshape(mnb),
                             np.asarray(logc_uns_j).reshape(mnb)])
        cost = np.asarray(cfg.cost_weight
                          * policies.policy_concentration_cost(topo),
                          np.float32)
        ptable = np.asarray(policies.policy_table(topo), np.float32)
    err_ix = topo.modalities.index("error")
    err_decay = 0.5 ** (cfg.fast_period_s / cfg.error_ema_halflife_s)
    u_c = cfg.b_prior_uniform / s
    d_c = cfg.b_prior_sticky
    masked_obs = emits_mask or obs_valid is not None or restart_blackout

    # ---- kernel ----------------------------------------------------------
    def kernel(t0_ref, *refs):
        it = iter(refs)
        res = {n: next(it) for n in TAPE} if j_res else None
        (colsum_ref, proj_ref, projsum_ref, logna_ref, belief_ref, pa_ref,
         scal_ref, obsm_ref, tutil_ref, envk_ref, envr_ref, pstack_ref,
         arr_ref, haz_ref, unif_ref, gum_ref, sftbl_ref, logc_ref, cost_ref,
         ptab_ref) = (next(it) for _ in range(20))
        ov_ref = next(it) if obs_valid is not None else None
        hbm = {n: next(it) for n in TAPE} if streamed else None
        qn_out, tr_act, tr_r, tr_rk, tr_rm, envk_out, envr_out = (
            next(it) for _ in range(7))
        if streamed:
            bufs = {n: next(it) for n in TAPE}
            sems = next(it)

        t0_v = t0_ref[0, 0]
        colsum = colsum_ref[...]                                 # (BR,A,S)
        ptab = ptab_ref[...]                                     # (A, K)

        servers, mu_t, svc_mean, p95f, queue_cap, p_unst = (
            pstack_ref[0], pstack_ref[1], pstack_ref[2], pstack_ref[3],
            pstack_ref[4], pstack_ref[5])
        r_base, r_load, r_knee, r_shock, r_min, r_max = (
            pstack_ref[6], pstack_ref[7], pstack_ref[8], pstack_ref[9],
            pstack_ref[10], pstack_ref[11])
        timeout_s = pstack_ref[12][:, 0:1]
        a_lat = jnp.minimum(1.0, 2.0 * dt / pstack_ref[13][:, 0:1])
        a_err = jnp.minimum(1.0, 2.0 * dt / pstack_ref[14][:, 0:1])
        a_rps = jnp.minimum(1.0, 2.0 * dt / pstack_ref[15][:, 0:1])
        cap_rate = servers * mu_t

        act_iota = jax.lax.broadcasted_iota(jnp.int32, (1, a_n), 1)
        bin_lane = jax.lax.broadcasted_iota(jnp.int32, (1, mnb), 1)

        def rowsum(x):
            return jnp.sum(x, axis=-1, keepdims=True)

        def count_ge(col, edges):
            b = jnp.zeros(col.shape, jnp.int32)
            for e in edges:
                b = b + (col >= e).astype(jnp.int32)
            return b

        # the chunks that hold a filled slot (slot < t0), of the resident
        # prefix and of the streamed rest: a fold reads no other
        if j_res:
            n_res, res_tail_live = live_chunks(t0_v, 0, j_res, j_chunk)
        if streamed:
            n_live, tail_live = live_chunks(t0_v, j_res, j, j_chunk)
            row0 = pl.multiple_of(pl.program_id(0) * br, br)
            # a traced zero: the padded copies reach past the logical shape,
            # which only the static bound check would refuse
            zero = 0 if interpret else t0_v * 0
            lane0 = zero if interpret else pl.multiple_of(zero, 128)

        def copies(names, start, rows, b):
            """Copies of the tape rows [start, start + rows) of ``names``
            into buffer ``b``."""
            return [pltpu.make_async_copy(
                hbm[n].at[pl.ds(row0, br), pl.ds(start, rows),
                          pl.ds(lane0, lanes[n])],
                bufs[n].at[b, :, pl.ds(0, rows), :],
                sems.at[TAPE.index(n), b]) for n in names]

        def chunk_start(c):
            return pl.multiple_of(j_res + c * j_chunk, j_chunk)

        def when(live, fold, acc):
            return jax.lax.cond(live, fold, lambda a: a, acc)

        def over_slots(body, init, names):
            """Fold ``body(tape, acc)`` over the live chunks of the tape,
            where ``tape(name)`` loads the chunk of one tape operand: those
            of the resident prefix from its VMEM blocks, then the streamed
            ones, copied two buffers deep (the copy of chunk c+1 overlaps
            the fold of chunk c, and the first copy the resident fold)."""
            if streamed:
                @pl.when(n_live > 0)
                def _():
                    for cp in copies(names, chunk_start(0), j_chunk, 0):
                        cp.start()
            acc = init
            if j_res:
                def resident(start, size):
                    return lambda n: res[n][:, pl.ds(start, size), :]
                n_full, tail = divmod(j_res, j_chunk)
                if n_full == 1:
                    acc = when(n_res > 0,
                               lambda a: body(resident(0, j_chunk), a), acc)
                else:
                    acc = jax.lax.fori_loop(
                        0, n_res, lambda c, a: body(resident(
                            pl.multiple_of(c * j_chunk, j_chunk), j_chunk),
                            a), acc)
                if tail:
                    acc = when(res_tail_live, lambda a: body(
                        resident(n_full * j_chunk, tail), a), acc)
            if not streamed:
                return acc

            def buffered(b, size):
                return lambda n: bufs[n][b, :, pl.ds(0, size), :widths[n]]

            def step(c, acc):
                b = c % 2

                @pl.when(c + 1 < n_live)
                def _():
                    for cp in copies(names, chunk_start(c + 1), j_chunk,
                                     1 - b):
                        cp.start()
                for cp in copies(names, chunk_start(c), j_chunk, b):
                    cp.wait()
                return body(buffered(b, j_chunk), acc)

            acc = jax.lax.fori_loop(0, n_live, step, acc)
            if tail_s:
                def tail_chunk(acc):
                    start = (j - tail_s if interpret
                             else pl.multiple_of(j - tail_s + zero, 8))
                    cps = copies(names, start, tail_rows, 0)
                    for cp in cps:
                        cp.start()
                    for cp in cps:
                        cp.wait()
                    return body(buffered(0, tail_s), acc)
                acc = when(tail_live, tail_chunk, acc)
            return acc

        def tick(w, c, select: bool):
            """One fast tick at window offset ``w`` (static or traced);
            ``select`` compiles the EFE + sampling path (dwell boundary)."""
            t_idx = t0_v + w
            raw_obs, obs_mask, belief = c["raw_obs"], c["obs_mask"], c["belief"]
            prev_action = c["prev_action"]
            mask = obs_mask if emits_mask else None

            # ---- observe: discretize published telemetry + util scrape
            obs_bins = [count_ge(raw_obs[:, i:i + 1], edges_list[i])
                        for i in range(m)]                       # M x (BR,1)
            # utilization bins in state-factor order (heaviest tier first)
            util_bins = [count_ge(c["tier_util"][:, k_t - 1 - i:k_t - i],
                                  uedges) for i in range(k_t)]
            util_valid = ((t_idx % util_period) == 0) & (t_idx > 0)

            # ---- adaptive-preference error EMA (holds when masked)
            error_ema = (err_decay * c["error_ema"]
                         + (1.0 - err_decay) * raw_obs[:, err_ix:err_ix + 1])
            if mask is not None:
                error_ema = jnp.where(mask[:, err_ix:err_ix + 1] > 0,
                                      error_ema, c["error_ema"])
            unstable = error_ema > cfg.error_trigger             # (BR, 1)

            # ---- evidence: one-hot A gather + gated utilization scrape
            loglik = jnp.zeros_like(belief)
            for m_i in range(m):
                pm = jnp.zeros_like(belief)
                for b_i in range(nb):
                    sel = (obs_bins[m_i] == b_i).astype(jnp.float32)
                    pm = pm + sel * logna_ref[:, m_i, b_i, :]
                if mask is not None:
                    pm = pm * mask[:, m_i:m_i + 1]
                loglik = loglik + pm
            util_ll = jnp.zeros_like(belief)
            for k in range(k_t):
                match = sftbl_ref[k:k + 1, :] == util_bins[k]    # (BR, S)
                util_ll = util_ll + jnp.log(jnp.where(
                    match, 1.0 - eps_u, eps_u / (topo.n_levels - 1)))
            loglik = loglik + jnp.where(util_valid, util_ll, 0.0)

            # ---- factored belief update (prior never materializes B)
            oh_pa = (prev_action == act_iota).astype(jnp.float32)  # (BR, A)
            csum = jnp.sum(oh_pa[:, :, None] * colsum, axis=1)   # (BR, S)
            qt = belief / csum

            def prior_slots(tape, acc):
                cw = jnp.sum(tape("coefact") * oh_pa[:, None, :],
                             axis=-1, keepdims=True)             # (BR,Jc,1)
                qp = tape("qp").astype(jnp.float32)
                pend_p = cw * _batched_dot(qp, qt[:, None, :], 2, 2)
                return acc + jnp.sum(
                    pend_p * tape("qn").astype(jnp.float32), axis=1)

            slot = over_slots(prior_slots, jnp.zeros_like(belief),
                              _PRIOR_TAPE)
            num = u_c * rowsum(qt) + d_c * qt + slot
            prior = num / jnp.maximum(rowsum(num), 1e-30)
            logp = loglik + jnp.log(jnp.maximum(prior, 1e-30))
            logp = logp - jnp.max(logp, axis=-1, keepdims=True)
            q_un = jnp.exp(logp)
            q_next = q_un / jnp.maximum(rowsum(q_un), 1e-30)

            # ---- EFE + categorical via pre-drawn Gumbel (selecting ticks)
            if select:
                logc = jnp.where(unstable, logc_ref[1:2, :],
                                 logc_ref[0:1, :])               # (BR, M·NB)
                qa = q_next[:, None, :] / colsum                 # (BR, A, S)
                sqa = jnp.sum(qa, axis=-1)                       # (BR, A)

                def efe_slots(tape, acc):
                    qp = tape("qp").astype(jnp.float32)
                    pend = tape("coefact") * _batched_dot(qp, qa, 2, 2)
                    return acc + _batched_dot(pend, tape("qnproj"), 1, 1)
                # slot terms of the (P) projections and, last, of Σ_t ŝ
                o_slot = over_slots(
                    efe_slots, jnp.zeros((br, a_n, p_n + 1), jnp.float32),
                    _EFE_TAPE)
                o_num = (u_c * sqa[:, :, None]
                         * projsum_ref[...][:, None, :]
                         + d_c * _batched_dot(qa, proj_ref[...], 2, 2)
                         + o_slot[:, :, :p_n])                   # (BR, A, P)
                sden = jnp.maximum((u_c * s + d_c) * sqa + o_slot[:, :, p_n],
                                   1e-30)                        # (BR, A)
                o_pred = o_num / sden[:, :, None]
                o_obs = o_pred[:, :, :mnb]
                terms = jnp.where(
                    o_obs > 1e-20,
                    o_obs * (jnp.log(jnp.maximum(o_obs, 1e-30))
                             - logc[:, None, :]), 0.0)
                amb_rows = o_pred[:, :, mnb:]                    # (BR, A, M)
                if mask is not None:
                    maskb = jnp.zeros((br, mnb), jnp.float32)
                    for m_i in range(m):
                        in_m = ((bin_lane >= m_i * nb)
                                & (bin_lane < (m_i + 1) * nb))
                        maskb = jnp.where(in_m, mask[:, m_i:m_i + 1], maskb)
                    terms = terms * maskb[:, None, :]
                    ambiguity = jnp.sum(amb_rows * mask[:, None, :],
                                        axis=-1)
                else:
                    ambiguity = jnp.sum(amb_rows, axis=-1)
                g = jnp.sum(terms, axis=-1) + ambiguity + cost_ref[...]
                probs = jax.nn.softmax(-cfg.beta * g, axis=-1)
                action = jnp.argmax(
                    jnp.log(jnp.maximum(probs, 1e-30)) + gum_ref[w],
                    axis=-1, keepdims=True).astype(jnp.int32)    # (BR, 1)
            else:
                action = prev_action

            # ---- dwell gate (selecting structure is static per window)
            dtc = jnp.where(action != prev_action, 0.0,
                            c["dtc"] + cfg.fast_period_s)
            obs_frac = jnp.mean(obs_mask, axis=-1, keepdims=True)
            qn_out[w] = q_next

            # ---- routing weights (one-hot row select) + fluid env window
            backlog, down_left = c["backlog"], c["down_left"]
            oh_act = (action == act_iota).astype(jnp.float32)
            weights = jnp.sum(oh_act[:, :, None] * ptab[None], axis=1)
            w_n = jnp.maximum(weights, 0.0)
            w_n = w_n / jnp.maximum(rowsum(w_n), 1e-12)
            up = down_left <= _EPS
            upf = up.astype(jnp.float32)
            arr_w = arr_ref[w]                                   # (BR, 1)
            lam = w_n * arr_w
            arr_mass = lam * dt
            refused = rowsum(arr_mass * (1.0 - upf))
            cap = cap_rate * dt * upf
            avail = backlog + arr_mass * upf
            served = jnp.minimum(avail, cap)
            backlog1 = avail - served
            over = jnp.maximum(backlog1 - (queue_cap + servers), 0.0)
            backlog1 = backlog1 - over
            wait = jnp.where(
                cap_rate > 0,
                0.5 * (backlog + backlog1) / jnp.maximum(cap_rate, _EPS),
                0.0)
            tier_latency = wait + svc_mean
            tier_p95 = wait + svc_mean * p95f
            timed_out = jnp.where(tier_latency > timeout_s, served, 0.0)
            completed = served - timed_out
            util = jnp.where(cap > 0,
                             served / jnp.maximum(cap_rate * dt, _EPS), 0.0)
            util_accum = c["util_accum"] + util * dt
            scrape_now = ((t_idx + 1) % scrape_every) == 0
            util_scrape = jnp.where(scrape_now,
                                    util_accum / (scrape_every * dt),
                                    c["util_scrape"])
            util_accum = jnp.where(scrape_now, 0.0, util_accum)
            hazard_w = haz_ref[w] * p_unst * (
                r_base
                + r_load * jnp.maximum(0.0, util_scrape - r_knee)
                + r_shock * jnp.maximum(0.0, lam - c["prev_tier_rps"])
                / jnp.maximum(cap_rate, _EPS))
            p_restart = 1.0 - jnp.exp(-hazard_w * dt)
            restarted = (up & (unif_ref[w, 0] < p_restart)).astype(
                jnp.float32)
            killed = backlog1 * restarted
            backlog = backlog1 * (1.0 - restarted)
            dur = r_min + unif_ref[w, 1] * (r_max - r_min)
            down_left = jnp.maximum(down_left - dt, 0.0)
            down_left = jnp.where(restarted > 0, dur, down_left)

            win_success = rowsum(completed)
            win_fail = (refused + rowsum(over) + rowsum(timed_out)
                        + rowsum(killed))

            # completion-weighted P95, sort-free: the atom whose cumulative
            # completion mass (under the stable lat-then-index order the
            # oracle's argsort induces) crosses 0.95
            tot = jnp.maximum(win_success, _EPS)
            p95_win = jnp.zeros_like(tot)
            for i in range(k_t):
                p_i = tier_p95[:, i:i + 1]
                c_i = jnp.zeros_like(tot)
                for jj in range(k_t):
                    p_j = tier_p95[:, jj:jj + 1]
                    done_j = completed[:, jj:jj + 1]
                    if jj == i:
                        c_i = c_i + done_j
                        continue
                    before = p_j < p_i
                    if jj < i:
                        before = before | (p_j == p_i)
                    c_i = c_i + jnp.where(before, done_j, 0.0)
                first = ((c_i / tot >= 0.95)
                         & ((c_i - completed[:, i:i + 1]) / tot < 0.95))
                p95_win = p95_win + jnp.where(first, p_i, 0.0)

            p95_ema = jnp.where(win_success > _EPS,
                                (1 - a_lat) * c["p95_ema"] + a_lat * p95_win,
                                c["p95_ema"])
            total_win = win_success + win_fail
            err_frac = win_fail / jnp.maximum(total_win, _EPS)
            err_ema_env = jnp.where(total_win > _EPS,
                                    (1 - a_err) * c["err_ema_env"]
                                    + a_err * err_frac, c["err_ema_env"])
            rps_ema = (1 - a_rps) * c["rps_ema"] + a_rps * arr_w
            tier_queue = jnp.maximum(backlog - servers, 0.0)
            fresh = jnp.concatenate([p95_ema, rps_ema, rowsum(tier_queue),
                                     err_ema_env], axis=-1)      # (BR, M)
            if not masked_obs:
                win_mask = jnp.ones_like(fresh)
                published = fresh
            else:
                win_mask = (ov_ref[w] if obs_valid is not None
                            else jnp.ones_like(fresh))
                if restart_blackout:
                    cell_up = jnp.max(down_left, axis=-1,
                                      keepdims=True) <= _EPS
                    win_mask = win_mask * cell_up.astype(jnp.float32)
                    util_scrape = jnp.where(cell_up, util_scrape,
                                            c["util_scrape"])
                published = jnp.where(win_mask > 0, fresh, c["held_obs"])

            tr_act[w] = action
            tr_r[w] = jnp.concatenate(
                [win_success, win_fail, unstable.astype(jnp.float32),
                 obs_frac, dtc, error_ema], axis=-1)
            tr_rk[w, 0] = weights
            tr_rk[w, 1] = util_scrape
            tr_rk[w, 2] = (down_left <= _EPS).astype(jnp.float32)
            tr_rk[w, 3] = tier_queue
            tr_rk[w, 4] = tier_latency
            tr_rk[w, 5] = tier_p95
            tr_rk[w, 6] = completed
            tr_rk[w, 7] = restarted
            tr_rm[w, 0] = published
            tr_rm[w, 1] = win_mask
            tr_rm[w, 2] = raw_obs

            acct = jnp.concatenate(
                [rowsum(arr_mass), win_success, rowsum(timed_out),
                 rowsum(over), refused, rowsum(killed)], axis=-1)
            return dict(
                belief=q_next, prev_action=action, dtc=dtc,
                error_ema=error_ema, raw_obs=published,
                obs_mask=win_mask if emits_mask else obs_mask,
                held_obs=published, tier_util=util_scrape,
                backlog=backlog, down_left=down_left, util_accum=util_accum,
                util_scrape=util_scrape, prev_tier_rps=lam,
                tier_requests=c["tier_requests"] + arr_mass,
                tier_success=c["tier_success"] + completed,
                n_restarts=c["n_restarts"] + restarted,
                p95_ema=p95_ema, rps_ema=rps_ema, err_ema_env=err_ema_env,
                acct=c["acct"] + acct)

        carry = dict(
            belief=belief_ref[...], prev_action=pa_ref[...],
            dtc=scal_ref[:, 0:1], error_ema=scal_ref[:, 1:2],
            raw_obs=obsm_ref[0], obs_mask=obsm_ref[1], held_obs=obsm_ref[2],
            tier_util=tutil_ref[...],
            backlog=envk_ref[0], down_left=envk_ref[1],
            util_accum=envk_ref[2], util_scrape=envk_ref[3],
            prev_tier_rps=envk_ref[4], tier_requests=envk_ref[5],
            tier_success=envk_ref[6], n_restarts=envk_ref[7],
            p95_ema=envr_ref[:, 0:1], rps_ema=envr_ref[:, 1:2],
            err_ema_env=envr_ref[:, 2:3], acct=envr_ref[:, 3:9])

        # dwell blocks: a selecting tick, then dwell-1 held ticks (t0 sits
        # on a dwell boundary); the tail block of a short window is unrolled
        def dwell_block(b, c):
            w0 = b * dwell
            c = tick(w0, c, select=True)
            return jax.lax.fori_loop(
                1, dwell, lambda i, cc: tick(w0 + i, cc, select=False), c)

        n_blocks, tail = divmod(w_ticks, dwell)
        carry = jax.lax.fori_loop(0, n_blocks, dwell_block, carry)
        for i in range(tail):
            carry = tick(n_blocks * dwell + i, carry, select=(i == 0))

        # ---- final env carries back to HBM (once per window, not per tick)
        for i, name in enumerate(("backlog", "down_left", "util_accum",
                                  "util_scrape", "prev_tier_rps",
                                  "tier_requests", "tier_success",
                                  "n_restarts")):
            envk_out[i] = carry[name]
        envr_out[...] = jnp.concatenate(
            [carry["p95_ema"], carry["rps_ema"], carry["err_ema_env"],
             carry["acct"]], axis=-1)

    # ---- operands (cell axis on the sublanes, padded to the block) --------
    def draws(k):
        k_fire, k_dur = jax.random.split(k)
        return jnp.stack([jax.random.uniform(k_fire, (r, k_t)),
                          jax.random.uniform(k_dur, (r, k_t))])
    uniforms = jax.vmap(draws)(k_env)                            # (W,2,R,K)

    pstack = jnp.stack(
        [params.servers, params.mu, params.service_mean_s,
         params.service_p95_factor, params.queue_cap, params.unstable,
         params.restart_base, params.restart_load, params.restart_knee,
         params.restart_shock, params.restart_min_s, params.restart_max_s]
        + [jnp.broadcast_to(v, (r, k_t)) for v in
           (params.timeout_s, params.latency_window_s,
            params.error_window_s, params.rps_window_s)])        # (16,R,K)
    envk = jnp.stack([est.backlog, est.down_left, est.util_accum,
                      est.util_scrape, est.prev_tier_rps,
                      est.tier_requests, est.tier_success,
                      est.n_restarts])                           # (8, R, K)
    envr = jnp.stack([est.p95_ema, est.rps_ema, est.err_ema,
                      est.n_requests, est.n_success, est.err_timeout,
                      est.err_overflow, est.err_refused,
                      est.err_restart], axis=-1)                 # (R, 9)
    raw_obs0, tier_util0, tier_up0, tier_queue0, obs_mask0 = obs_carry
    obsm = jnp.stack([raw_obs0, obs_mask0, est.held_obs])        # (3, R, M)

    # the tape, in TAPE order, cell axis first
    tape = [pad_cells(x, r_pad) for x in (
        slots.q_prev, slots.q_next,
        jnp.concatenate([cache.qnproj, cache.sumqn[..., None]], axis=-1),
        cache.coefact)]
    # (operand, cell axis); the block spans the cell axis in router blocks
    # and every other axis whole
    cells = [
        (cache.colsum, 0), (cache.proj, 0), (cache.projsum, 0),
        (cache.logna, 0), (state.belief, 0), (state.prev_action[:, None], 0),
        (jnp.stack([state.dt_since_change, state.error_ema], axis=-1), 0),
        (obsm, 1), (tier_util0, 0), (envk, 1), (envr, 0), (pstack, 1),
        (arrival[..., None], 1), (hazard, 1), (uniforms, 2), (gumbel, 1),
    ]
    n_cells = len(cells)
    shared = [sf_tbl, logc_tbl, cost[None], ptable]
    if obs_valid is not None:
        cells.append((jnp.asarray(obs_valid, jnp.float32), 1))

    def cell_spec(shape, axis):
        block = shape[:axis] + (br,) + shape[axis + 1:]
        zeros = (0,) * len(shape)
        return pl.BlockSpec(
            block, lambda i: zeros[:axis] + (i,) + zeros[axis + 1:])

    def full_spec(shape):
        return pl.BlockSpec(shape, lambda i: (0,) * len(shape))

    cell_ops = [pad_cells(x, r_pad, axis) for x, axis in cells]
    cell_specs = [cell_spec(x.shape, axis)
                  for x, (_, axis) in zip(cell_ops, cells)]
    # the resident prefix: the first j_res slots of each router block
    res_specs = [pl.BlockSpec((br, j_res) + x.shape[2:], lambda i: (i, 0, 0))
                 for x in tape] if j_res else []
    hbm_specs = [pl.BlockSpec(memory_space=pl.ANY)] * 4 if streamed else []
    in_specs = ([pl.BlockSpec(memory_space=pltpu.SMEM)] + res_specs
                + cell_specs[:n_cells] + [full_spec(c.shape) for c in shared]
                + cell_specs[n_cells:] + hbm_specs)
    operands = ([jnp.asarray(t0, jnp.int32).reshape(1, 1)]
                + (tape if j_res else [])
                + cell_ops[:n_cells] + [jnp.asarray(c) for c in shared]
                + cell_ops[n_cells:] + (tape if streamed else []))
    scratch = ([pltpu.VMEM((2, br, j_chunk, lanes[n]), dt)
                for n, (_, dt) in zip(TAPE, plan.widths)]
               + [pltpu.SemaphoreType.DMA((len(TAPE), 2))]
               if streamed else [])

    out_cells = [
        ((w_ticks, r_pad, s), jnp.float32, 1),           # posteriors
        ((w_ticks, r_pad, 1), jnp.int32, 1),             # applied action
        ((w_ticks, r_pad, 6), jnp.float32, 1),           # per-cell scalars
        ((w_ticks, 8, r_pad, k_t), jnp.float32, 2),      # per-tier trace
        ((w_ticks, 3, r_pad, m), jnp.float32, 2),        # telemetry trace
        ((8, r_pad, k_t), jnp.float32, 1),               # env (R, K) carry
        ((r_pad, 9), jnp.float32, 0),                    # env (R,) carry
    ]
    out_shapes = [jax.ShapeDtypeStruct(sh, dt_) for sh, dt_, _ in out_cells]
    out_specs = [cell_spec(sh, ax) for sh, _, ax in out_cells]


    outs = pl.pallas_call(
        kernel,
        name="aif_mega_window",
        grid=(r_pad // br,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shapes,
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",), vmem_limit_bytes=plan.vmem),
        interpret=interpret,
    )(*operands)
    with jax.named_scope("aif.window.land"):
        qn_w, tr_act, tr_r, tr_rk, tr_rm, envk_o, envr_o = outs
        qn_w = qn_w[:, :r]                                       # (W, R, S)
        tr_act, tr_r = tr_act[:, :r, 0], tr_r[:, :r]
        tr_rk, tr_rm = tr_rk[:, :, :r], tr_rm[:, :, :r]
        envk_o, envr_o = envk_o[:, :r], envr_o[:r]

        # ---- land the window's slot block (slot index == global tick) ----
        def prepend(first, per_tick):
            return jnp.concatenate([first[None], per_tick[:-1]], axis=0)

        push_mask = (prepend(obs_mask0, tr_rm[:, 1]) if emits_mask
                     else jnp.ones((w_ticks,) + obs_mask0.shape, jnp.float32))
        pushes = dict(
            q_prev=prepend(state.belief, qn_w), q_next=qn_w,
            obs_bins=spaces.discretize_observation(tr_rm[:, 2], disc),
            obs_mask=push_mask,
            action=prepend(state.prev_action, tr_act),
            dt_since_change=prepend(state.dt_since_change, tr_r[..., 4]))
        new_slots = slots._replace(**{
            name: jax.lax.dynamic_update_slice_in_dim(
                getattr(slots, name),
                jnp.swapaxes(val, 0, 1).astype(getattr(slots, name).dtype),
                t0, axis=1)
            for name, val in pushes.items()})

        new_state = state._replace(
            slots=new_slots, belief=qn_w[-1], prev_action=tr_act[-1],
            dt_since_change=tr_r[-1, :, 4], error_ema=tr_r[-1, :, 5],
            unstable=tr_r[-1, :, 2] > 0.5, t=state.t + w_ticks)
        new_est = batched.FluidState(
            backlog=envk_o[0], down_left=envk_o[1], util_accum=envk_o[2],
            util_scrape=envk_o[3], prev_tier_rps=envk_o[4],
            p95_ema=envr_o[:, 0], rps_ema=envr_o[:, 1], err_ema=envr_o[:, 2],
            held_obs=tr_rm[-1, 0],
            n_requests=envr_o[:, 3], n_success=envr_o[:, 4],
            err_timeout=envr_o[:, 5], err_overflow=envr_o[:, 6],
            err_refused=envr_o[:, 7], err_restart=envr_o[:, 8],
            tier_requests=envk_o[5], tier_success=envk_o[6],
            n_restarts=envk_o[7])
        win = batched.WindowInfo(
            raw_obs=tr_rm[:, 0], obs_mask=tr_rm[:, 1],
            tier_utilization=tr_rk[:, 1], tier_up=tr_rk[:, 2],
            tier_queue=tr_rk[:, 3], tier_latency_s=tr_rk[:, 4],
            tier_p95_s=tr_rk[:, 5], tier_completed=tr_rk[:, 6],
            success=tr_r[..., 0], failures=tr_r[..., 1], restarted=tr_rk[:, 7])
        trace = (tr_act, tr_rk[:, 0], tr_rm[:, 2], tr_r[..., 2] > 0.5,
                 tr_r[..., 3], win)
        new_carry = (tr_rm[-1, 0], tr_rk[-1, 1], tr_rk[-1, 2], tr_rk[-1, 3],
                     tr_rm[-1, 1] if emits_mask else obs_mask0)
    return new_state, new_est, new_carry, trace
