"""Fused fleet-EFE Pallas TPU kernel, shape-generic over (S, A, M, bins).

The paper's action-selection hot loop — ``B_a·q → A·ŝ → risk/ambiguity`` —
batched over a fleet of R routers (one per service cell) at 1 Hz.  Per
(router-block, action) grid step the kernel keeps one action's transition
tile (BR, S, S) in VMEM and fuses the mat-vec, the observation projection
and the risk/ambiguity reductions, so predicted state/observation
distributions never round-trip to HBM.

Every dimension derives from the input shapes, which in turn derive from the
:class:`~repro.core.topology.Topology`.  The state axis keeps its true width
S (243 for the paper's 3-tier topology: blocks that span a whole axis may be
unaligned, and Mosaic masks the partial lane tile), so the (R, A, S, S)
transition stack is read in place, never padded or copied.  The fleet axis
R is padded to the router block, a multiple of the 8 sublanes (padded rows
repeat the last router and are cropped from the result).

TPU layout: the cell axis sits on the sublane dim of every block, never on
the lane dim; the (M, NB) observation axes are flattened in the wrapper
(Mosaic cannot reshape an unaligned tiled dim); the per-action cost lives
in SMEM and G is accumulated into one full-width (BR, A) output block
across the sequential action axis.  The mat-vecs are VPU multiply-and-sum
reductions in float32, so no MXU precision mode enters the result.

VMEM at BR=8, S=243: the B tile is 8·243·256·4 B = 2.0 MB (lane-padded),
double-buffered, plus small operands — well inside the default scoped limit.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128         # TPU lane width (VMEM tiles pad the minor dim to it)
SUBLANES = 8         # TPU sublane count: router blocks are multiples of this

#: Per-core VMEM of a TPU v5e (and v6e) TensorCore; the scoped limit a
#: kernel may request is capped below it.
VMEM_CAPACITY = 128 * 1024 * 1024


def pad_rows(r: int, block_r: int = SUBLANES) -> int:
    """R rounded up to a whole number of router blocks."""
    return -(-r // block_r) * block_r


def pad_cells(x: jnp.ndarray, r_pad: int, axis: int = 0) -> jnp.ndarray:
    """Pad the cell axis to ``r_pad`` rows by repeating the last router.

    Padded rows are finite copies of a real router (every op in these
    kernels is row-local), and the wrappers crop them from the results.
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


def _efe_compute(b, q, a_proj, logc, amb, cost, maskb=None):
    """Shared EFE math for one (router-block, action) tile.

    b: (BR, S, S) transition tile, q: (BR, S) beliefs,
    a_proj: (BR, M·NB, S) observation rows, logc: (BR, M·NB), amb: (BR, S),
    cost: () this action's Cost(a), maskb: optional (BR, M·NB)
    observation-validity mask broadcast over bins — masked modalities drop
    out of the risk reduction (ambiguity masking happens upstream via the
    effective ``amb`` operand).  Returns G (BR, 1).
    """
    # ŝ_a = B_a q: VPU multiply + lane reduction per predicted state
    s_pred = jnp.sum(b * q[:, None, :], axis=-1)               # (BR, S)
    s_pred = s_pred / jnp.maximum(
        jnp.sum(s_pred, axis=-1, keepdims=True), 1e-30)
    # ô_m = A_m ŝ_a for every modality/bin
    o_pred = jnp.sum(a_proj * s_pred[:, None, :], axis=-1)     # (BR, M·NB)

    terms = jnp.where(
        o_pred > 1e-20,
        o_pred * (jnp.log(jnp.maximum(o_pred, 1e-30)) - logc),
        0.0)
    if maskb is not None:
        terms = terms * maskb
    risk = jnp.sum(terms, axis=-1, keepdims=True)              # (BR, 1)
    ambiguity = jnp.sum(s_pred * amb, axis=-1, keepdims=True)
    return risk + ambiguity + cost


def _store_action_column(out_ref, g):
    """Write G (BR, 1) into column ``a`` of the resident (BR, A) block (the
    action axis is the innermost, sequential grid dimension)."""
    a = pl.program_id(1)
    lane = jax.lax.broadcasted_iota(jnp.int32, out_ref.shape, 1)
    out_ref[...] = jnp.where(lane == a, g, out_ref[...])


def _efe_kernel(b_ref, q_ref, a_ref, logc_ref, amb_ref, cost_ref, out_ref):
    """One (router-block, action) grid step.

    b_ref:    (BR, 1, S, S)    transition tile for this action
    q_ref:    (BR, S)          beliefs
    a_ref:    (BR, M·NB, S)    observation model rows
    logc_ref: (BR, M·NB)       log-preferences
    amb_ref:  (BR, S)          per-state ambiguity
    cost_ref: (1, A) SMEM      Cost(a) of every action
    out_ref:  (BR, A)          G, resident across the action axis
    """
    g = _efe_compute(b_ref[:, 0], q_ref[...], a_ref[...], logc_ref[...],
                     amb_ref[...], cost_ref[0, pl.program_id(1)])
    _store_action_column(out_ref, g)


def _efe_kernel_masked(b_ref, q_ref, a_ref, logc_ref, mask_ref, amb_ref,
                       cost_ref, out_ref):
    """Mask-aware twin of :func:`_efe_kernel`.

    mask_ref: (BR, M·NB) per-modality observation-validity, pre-broadcast
    over bins; the ``amb`` operand is expected to already be the
    mask-effective ambiguity (see ``repro.core.generative.masked_ambiguity``).
    """
    g = _efe_compute(b_ref[:, 0], q_ref[...], a_ref[...], logc_ref[...],
                     amb_ref[...], cost_ref[0, pl.program_id(1)],
                     maskb=mask_ref[...])
    _store_action_column(out_ref, g)


def _fleet_operands(b_norm, q, a_norm, logc, amb, obs_mask, block_r):
    """Pad R to the router block, flatten (M, NB)."""
    r = b_norm.shape[0]
    m, nb = a_norm.shape[1], a_norm.shape[2]
    r_pad = pad_rows(r, block_r)
    a_proj = a_norm.reshape(r, m * nb, -1)
    logc = logc.reshape(r, m * nb)
    maskb = (None if obs_mask is None else jnp.repeat(
        obs_mask.astype(jnp.float32), nb, axis=-1))          # (R, M·NB)
    cells = [x.astype(jnp.float32)
             for x in (b_norm, q, a_proj, logc, amb)]
    if maskb is not None:
        cells.append(maskb)
    return r_pad, [pad_cells(x, r_pad) for x in cells]


def _check_block_r(block_r: int) -> None:
    if block_r <= 0 or block_r % SUBLANES:
        raise ValueError(f"block_r must be a positive multiple of "
                         f"{SUBLANES} (the TPU sublane count), got {block_r}")


@functools.partial(jax.jit, static_argnames=("block_r", "interpret"))
def efe_fleet_pallas(b_norm: jnp.ndarray, q: jnp.ndarray,
                     a_norm: jnp.ndarray, logc: jnp.ndarray,
                     amb: jnp.ndarray, cost: jnp.ndarray,
                     obs_mask: jnp.ndarray | None = None,
                     *, block_r: int = SUBLANES,
                     interpret: bool) -> jnp.ndarray:
    """G (R, A) for a fleet.  See ref.py for input semantics.

    Shape-generic: works for any (R, A, S, S) / (R, M, NB, S) operands; R is
    padded to a multiple of ``block_r`` (itself a multiple of 8)
    internally.

    ``obs_mask`` ((R, M) float 0/1) selects the mask-aware kernel: masked
    modalities drop out of the risk reduction, and the ``amb`` operand must
    then be the mask-effective ambiguity.  None compiles the exact unmasked
    kernel.

    ``interpret`` is deliberately required: only the :mod:`..ops` wrapper
    auto-detects the backend, so a direct caller can't silently run the
    interpret-mode emulator on a real TPU.
    """
    _check_block_r(block_r)
    r, a, s, _ = b_norm.shape
    mnb = a_norm.shape[1] * a_norm.shape[2]
    r_pad, operands = _fleet_operands(b_norm, q, a_norm, logc, amb,
                                      obs_mask, block_r)
    bspec = [
        pl.BlockSpec((block_r, 1, s, s), lambda i, j: (i, j, 0, 0)),
        pl.BlockSpec((block_r, s), lambda i, j: (i, 0)),
        pl.BlockSpec((block_r, mnb, s), lambda i, j: (i, 0, 0)),
        pl.BlockSpec((block_r, mnb), lambda i, j: (i, 0)),
        pl.BlockSpec((block_r, s), lambda i, j: (i, 0)),
    ]
    kernel = _efe_kernel
    if obs_mask is not None:
        kernel = _efe_kernel_masked
        bspec.insert(4, pl.BlockSpec((block_r, mnb), lambda i, j: (i, 0)))
        operands.insert(4, operands.pop())
    bspec.append(pl.BlockSpec(memory_space=pltpu.SMEM))
    operands.append(cost.astype(jnp.float32)[None, :])
    out = pl.pallas_call(
        kernel,
        grid=(r_pad // block_r, a),
        in_specs=bspec,
        out_specs=pl.BlockSpec((block_r, a), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((r_pad, a), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(*operands)
    return out[:r]


# ---------------------------------------------------------------------------
# Fused belief update → EFE (one control tick, belief never leaves VMEM)
# ---------------------------------------------------------------------------
def _belief_update_into_scratch(bprev_ref, qprev_ref, ll_ref, qout_ref,
                                q_scr):
    """Posterior (Eq. 2) at the first action step, parked in VMEM scratch.

    The action axis is the innermost (sequential) grid dimension, so the
    posterior for a router block is computed exactly once — at the first
    action step — and read from scratch for the remaining A-1 steps.  The
    observation-validity mask enters through ``ll_ref``: masked modalities
    were zeroed out of the summed log-likelihood before launch, so the
    VMEM-carried posterior already reflects only valid evidence.
    """
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        prior = jnp.sum(bprev_ref[...] * qprev_ref[...][:, None, :],
                        axis=-1)                               # (BR, S)
        prior = prior / jnp.maximum(
            jnp.sum(prior, axis=-1, keepdims=True), 1e-30)
        logp = ll_ref[...] + jnp.log(jnp.maximum(prior, 1e-30))
        logp = logp - jnp.max(logp, axis=-1, keepdims=True)
        qn = jnp.exp(logp)
        qn = qn / jnp.maximum(jnp.sum(qn, axis=-1, keepdims=True), 1e-30)
        q_scr[...] = qn
        qout_ref[...] = qn


def _belief_efe_kernel(bprev_ref, qprev_ref, ll_ref, b_ref, a_ref, logc_ref,
                       amb_ref, cost_ref, g_ref, qout_ref, q_scr):
    """One (router-block, action) grid step of the fused tick.

    bprev_ref: (BR, S, S)   previously-applied action's transition row
    qprev_ref: (BR, S)      beliefs before the tick
    ll_ref:    (BR, S)      observation log-likelihood
    b/a/logc/amb/cost/g:     as in :func:`_efe_kernel`
    qout_ref:  (BR, S)      posterior after the tick (written once)
    q_scr:     (BR, S)      VMEM scratch carrying q across action steps
    """
    _belief_update_into_scratch(bprev_ref, qprev_ref, ll_ref, qout_ref, q_scr)
    _efe_kernel(b_ref, q_scr, a_ref, logc_ref, amb_ref, cost_ref, g_ref)


def _belief_efe_kernel_masked(bprev_ref, qprev_ref, ll_ref, b_ref, a_ref,
                              logc_ref, mask_ref, amb_ref, cost_ref, g_ref,
                              qout_ref, q_scr):
    """Mask-aware twin of :func:`_belief_efe_kernel`: the mask already zeroed
    the per-modality evidence feeding the VMEM-carried posterior (via
    ``ll_ref``), and additionally drops masked modalities from the EFE risk
    reduction (``mask_ref``, (BR, M·NB)) — the ``amb`` operand carries the
    mask-effective ambiguity."""
    _belief_update_into_scratch(bprev_ref, qprev_ref, ll_ref, qout_ref, q_scr)
    _efe_kernel_masked(b_ref, q_scr, a_ref, logc_ref, mask_ref, amb_ref,
                       cost_ref, g_ref)


@functools.partial(jax.jit, static_argnames=("block_r", "interpret"))
def belief_efe_fleet_pallas(b_prev: jnp.ndarray, q_prev: jnp.ndarray,
                            loglik: jnp.ndarray, b_norm: jnp.ndarray,
                            a_norm: jnp.ndarray, logc: jnp.ndarray,
                            amb: jnp.ndarray, cost: jnp.ndarray,
                            obs_mask: jnp.ndarray | None = None,
                            *, block_r: int = SUBLANES,
                            interpret: bool
                            ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Fused (belief update → EFE) tick: (G (R, A), posterior q (R, S)).

    See :func:`repro.kernels.efe.ref.belief_efe_fleet_ref` for the input
    semantics and the matching XLA oracle.  With ``obs_mask`` ((R, M)) the
    mask-aware kernel runs: the caller supplies a ``loglik`` whose masked
    modalities are already zeroed (so the VMEM-carried posterior sees only
    valid evidence) and an ``amb`` that is the mask-effective ambiguity; the
    kernel itself drops masked modalities from the risk reduction.  As with
    :func:`efe_fleet_pallas`, ``interpret`` must be passed explicitly
    (the ops wrapper auto-detects the backend).
    """
    _check_block_r(block_r)
    r, a, s, _ = b_norm.shape
    mnb = a_norm.shape[1] * a_norm.shape[2]
    r_pad, efe_ops = _fleet_operands(b_norm, q_prev, a_norm, logc, amb,
                                     obs_mask, block_r)
    q_prev = efe_ops.pop(1)              # the EFE's q is the fused posterior
    operands = [pad_cells(b_prev.astype(jnp.float32), r_pad), q_prev,
                pad_cells(loglik.astype(jnp.float32), r_pad)] + efe_ops

    bspec = [
        pl.BlockSpec((block_r, s, s), lambda i, j: (i, 0, 0)),
        pl.BlockSpec((block_r, s), lambda i, j: (i, 0)),
        pl.BlockSpec((block_r, s), lambda i, j: (i, 0)),
        pl.BlockSpec((block_r, 1, s, s), lambda i, j: (i, j, 0, 0)),
        pl.BlockSpec((block_r, mnb, s), lambda i, j: (i, 0, 0)),
        pl.BlockSpec((block_r, mnb), lambda i, j: (i, 0)),
        pl.BlockSpec((block_r, s), lambda i, j: (i, 0)),
    ]
    kernel = _belief_efe_kernel
    if obs_mask is not None:
        kernel = _belief_efe_kernel_masked
        bspec.insert(6, pl.BlockSpec((block_r, mnb), lambda i, j: (i, 0)))
        operands.insert(6, operands.pop())
    bspec.append(pl.BlockSpec(memory_space=pltpu.SMEM))
    operands.append(cost.astype(jnp.float32)[None, :])
    g, q = pl.pallas_call(
        kernel,
        grid=(r_pad // block_r, a),
        in_specs=bspec,
        out_specs=[
            pl.BlockSpec((block_r, a), lambda i, j: (i, 0)),
            pl.BlockSpec((block_r, s), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((r_pad, a), jnp.float32),
            jax.ShapeDtypeStruct((r_pad, s), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((block_r, s), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(*operands)
    return g[:r], q[:r]
