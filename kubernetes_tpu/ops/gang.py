"""Gang placement: a whole pod group solved as ONE device dispatch.

SURVEY §7 step 7 calls the all-or-nothing gang feasibility check "strictly
easier on device than the reference's Permit-barrier dance": once
PreEnqueue quorum is met on the host, the gang's members are one batched
assignment problem — vmapped per-signature filter masks over the node
matrix, a sequential-greedy placement replay, and a single feasibility
reduction (`placed >= minCount`) that accepts or rejects the ENTIRE gang
atomically. The accepted gang commits through the async dispatcher with
no Reserve/Permit/Unreserve churn; the rejected gang unwinds ON DEVICE
(the returned carry is the input carry, leaf for leaf), so no member ever
holds partial resources — the classic gang-scheduling deadlock cannot
form.

Two tiers behind the one `run_gang` entry:

- **uniform tier** (`uniform=True`): a single-signature gang with the
  LeastAllocated strategy rides the closed-form top-L matrix
  (`program._uniform_core`, the run_uniform exactness argument verbatim)
  with the accept reduction bolted on — the whole 256-pod gang is one
  top_k, not 256 scan steps. Exactness flags are returned like
  run_uniform's; on a failed precondition the scheduler replays on the
  scan tier from the kept input carry.
- **scan tier** (`uniform=False`): the general program. Per-signature
  surfaces (filter masks + carry-independent scores) are hoisted ONCE via
  vmap over the gang's distinct signature rows [S]; the member scan then
  pays only normalization + argmax + a touched-row refresh per step —
  the SigCache fast-path cost, for every member, at any signature mix.

Topology-contiguous slice packing (Tesserae, arXiv:2508.04953): with
`w_contig > 0` the scan tier adds one more masked-argmax column — the
normalized count of gang members already placed in each node's topology
domain (`dom`, host-interned zone ids) — so a training gang prefers
filling domains it already occupies. The weight is 0 by default: the
default decision surface stays bit-identical to the serial Permit-barrier
oracle (the fuzzed parity gate in tests/test_gang_device.py holds
exactly that).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from ..perf.ledger import device_entry
from ..state.tensorize import NodeArrays
from .program import (Carry, PodTableDev, PodXs, ScoreConfig, _fit_scores,
                      _gather_row, _uniform_core, balanced_allocation,
                      default_normalize, fit_mask, least_allocated)


class GangXs(NamedTuple):
    """Per-member scan xs for one gang ([B] = pow2-padded member count)."""

    valid: jnp.ndarray   # bool [B] — member present (padding rows False)
    tidx: jnp.ndarray    # i32 [B] — row into PodTableDev
    widx: jnp.ndarray    # i32 [B] — slot into the gang's signature set [S]


def _run_gang_scan_impl(cfg: ScoreConfig, na: NodeArrays, carry: Carry,
                        xs: GangXs, table: PodTableDev, wt, needed, dom,
                        statics, w_contig: int):
    """Scan-tier gang assignment; returns (carry', packed i32[B+4]).

    packed[:B] holds each member's RAW greedy assignment (-1 = no feasible
    node) regardless of the gang verdict — the host commit needs to split
    quorum-unwound members from genuinely infeasible ones; packed[B] is
    the accept flag (placed >= needed), packed[B+1] the placed count, and
    packed[B+2:B+4] are always-true exactness flags (layout-compatible
    with the uniform tier). The carry update is CONDITIONAL: a rejected
    gang returns the input carry's values unchanged — the all-or-nothing
    unwind happens on device, with zero host round trips."""
    n = na.npods.shape[0]
    cols = jnp.array(cfg.score_cols, jnp.int32)
    nzmask = jnp.array(cfg.col_nonzero)
    slots = jnp.array(cfg.nonzero_slot, jnp.int32)

    # per-signature surfaces: the carry-INDEPENDENT kernels (static filter
    # mask, taint/affinity raw counts, ImageLocality) arrive precomputed —
    # the drain compiler's SurfaceCache hoists them once per node-state
    # statics generation, shared with the plan/wave programs. Gang rows
    # carry sig != 0 (no host ports), so the ports term the full
    # _slow_parts would fold in is vacuously true. Only the
    # carry-DEPENDENT fit/score columns evaluate here, at the gang's
    # entry state.
    static_m, taint_raw, na_raw, s_img = statics                # each [S, N]

    def _fit_parts(u):
        pod = _gather_row(table, PodXs(valid=jnp.bool_(True),
                                       sig=jnp.int32(0), tidx=u))
        fit_ok = fit_mask(na.cap, carry.used, carry.npods,
                          na.allowed_pods, pod.req)
        s_fit, s_bal = _fit_scores(cfg, na, carry, pod)
        return fit_ok, s_fit, s_bal

    fit_ok0, s_fit0, s_bal0 = jax.vmap(_fit_parts)(wt)          # each [S, N]
    req_s = table.req[wt]                                       # [S, R]
    nzreq_s = table.nonzero_req[wt]                             # [S, 2]
    skipb_s = table.skip_balanced[wt]                           # [S]

    def step(state, x: GangXs):
        used, nz, npods, fit_ok, s_fit, s_bal, domcnt, placed = state
        s = x.widx
        pod = _gather_row(table, PodXs(valid=x.valid, sig=jnp.int32(0),
                                       tidx=x.tidx))
        feasible = static_m[s] & fit_ok[s]
        s_taint = default_normalize(taint_raw[s], feasible, reverse=True)
        s_na = default_normalize(na_raw[s], feasible, reverse=False)
        total = (cfg.w_fit * s_fit[s] + cfg.w_balanced * s_bal[s]
                 + cfg.w_taint * s_taint + cfg.w_node_affinity * s_na
                 + cfg.w_image * s_img[s])
        if w_contig:
            # contiguity = one more masked-argmax column: members already
            # placed in the node's topology domain, DefaultNormalized
            total = total + w_contig * default_normalize(
                domcnt[dom].astype(jnp.int64), feasible, reverse=False)
        masked = jnp.where(feasible, total, jnp.int64(-1))
        best = jnp.argmax(masked).astype(jnp.int32)
        assigned = (masked[best] >= 0) & x.valid
        onehot = (jnp.arange(n, dtype=jnp.int32) == best) & assigned
        used2 = used + jnp.where(onehot[:, None], pod.req[None, :], 0)
        nz2 = nz + jnp.where(onehot[:, None], pod.nonzero_req[None, :], 0)
        npods2 = npods + onehot.astype(npods.dtype)

        # refresh the ONE touched row for every signature slot — the
        # gang-wide analog of program._row_refresh, same arithmetic
        cap_row = na.cap[best]
        used_row = used2[best]
        npods_row = npods2[best]
        nz_row = nz2[best]

        def _refresh(req, nzreq, skipb):
            fit_b = ((npods_row + 1 <= na.allowed_pods[best])
                     & jnp.all((req == 0) | (used_row + req <= cap_row)))
            cap_r = cap_row[cols][None, :]
            used_nz_r = nz_row[slots] + nzreq[slots]
            used_pl_r = used_row[cols] + req[cols]
            used_cols_r = jnp.where(nzmask, used_nz_r, used_pl_r)[None, :]
            s_fit_b = least_allocated(cfg, cap_r, used_cols_r)[0]
            s_bal_b = jnp.where(skipb, 0,
                                balanced_allocation(cap_r,
                                                    used_pl_r[None, :])[0])
            return fit_b, s_fit_b, s_bal_b

        fo_b, sf_b, sb_b = jax.vmap(_refresh)(req_s, nzreq_s, skipb_s)
        fit_ok2 = fit_ok.at[:, best].set(
            jnp.where(assigned, fo_b, fit_ok[:, best]))
        s_fit2 = s_fit.at[:, best].set(
            jnp.where(assigned, sf_b, s_fit[:, best]))
        s_bal2 = s_bal.at[:, best].set(
            jnp.where(assigned, sb_b, s_bal[:, best]))
        if w_contig:
            domcnt2 = domcnt.at[dom[best]].add(
                jnp.where(assigned, 1, 0).astype(domcnt.dtype))
        else:
            domcnt2 = domcnt
        placed2 = placed + assigned.astype(placed.dtype)
        return ((used2, nz2, npods2, fit_ok2, s_fit2, s_bal2, domcnt2,
                 placed2), jnp.where(assigned, best, jnp.int32(-1)))

    state0 = (carry.used, carry.nonzero_used, carry.npods,
              fit_ok0, s_fit0, s_bal0,
              jnp.zeros((n,), jnp.int32), jnp.int32(0))
    (used_f, nz_f, npods_f, _, _, _, _, placed), raw = lax.scan(
        step, state0, xs)
    accept = placed >= needed

    def sel(a, b):
        return jnp.where(accept, a, b)

    # the accepted gang's placements invalidate the resident SigCache
    # (its fit/score columns predate the gang); the rejected gang leaves
    # the carry — cache included — exactly as it arrived
    cache = carry.cache._replace(
        sig=jnp.where(accept, jnp.int32(0), carry.cache.sig))
    carry_out = carry._replace(used=sel(used_f, carry.used),
                               nonzero_used=sel(nz_f, carry.nonzero_used),
                               npods=sel(npods_f, carry.npods),
                               cache=cache)
    packed = jnp.concatenate([
        raw, jnp.stack([accept.astype(jnp.int32), placed,
                        jnp.int32(1), jnp.int32(1)])])
    return carry_out, packed


@functools.lru_cache(maxsize=None)
def _run_gang_scan_fn(donate: bool):
    return jax.jit(_run_gang_scan_impl,
                   static_argnames=("cfg", "w_contig"),
                   donate_argnums=(2,) if donate else ())


@functools.partial(jax.jit, static_argnames=("cfg", "L", "K", "J"))
def _run_gang_uniform_jit(cfg: ScoreConfig, na: NodeArrays, carry: Carry,
                          x: PodXs, table: PodTableDev, n_actual, needed,
                          L: int, K: int, J: int):
    """Closed-form tier: run_uniform's top-L matrix with the gang verdict
    reduction. The carry applies ONLY when the gang is accepted AND the
    exactness preconditions held — a rejected or precondition-failed run
    leaves the input carry untouched (the scheduler replays failed
    preconditions on the scan tier). packed layout matches the scan
    tier: [assignments(L); accept; placed; exact; depth]."""
    new_carry, assignments, ok, depth_ok = _uniform_core(
        cfg, na, carry, x, table, n_actual, L, K, J, None)
    placed = jnp.sum((assignments >= 0).astype(jnp.int32))
    accept = placed >= needed
    apply = accept & ok & depth_ok
    carry_out = jax.tree_util.tree_map(
        lambda a, b: jnp.where(apply, a, b), new_carry, carry)
    packed = jnp.concatenate([
        assignments,
        jnp.stack([accept, placed, ok, depth_ok]).astype(jnp.int32)])
    return carry_out, packed


@device_entry(donates=2, cost=(64.0, 4.0))
def run_gang(cfg: ScoreConfig, na: NodeArrays, carry: Carry, xs, table,
             wt=None, needed=None, dom=None, statics=None,
             w_contig: int = 0, uniform: bool = False, n_actual=None,
             L: int = 0, K: int = 0, J: int = 0):
    """JIT entry for whole-gang all-or-nothing assignment.

    `uniform=True` routes a single-signature gang to the closed-form tier
    (`xs` is then a one-row PodXs like run_uniform's, `n_actual` the true
    member count, L/K/J the matrix shape; never donates — the scheduler
    keeps the input carry to replay failed exactness preconditions on the
    scan tier). `uniform=False` runs the general scan tier (`xs` a
    GangXs, `wt` the i32[S] signature rows, `dom` the i32[N] topology
    domain ids for the contiguity column, `statics` the rows' hoisted
    carry-independent surfaces — the drain compiler's SurfaceCache rows,
    stacked [S, N] each exactly like run_plan's); the input carry is
    DONATED on accelerator backends exactly like run_batch — both the
    accept and the reject branch produce fresh output buffers, so the
    all-or-nothing unwind costs nothing. `needed` is the gang's remaining
    quorum (minCount minus already-assigned members), a dynamic i32 so
    quorum values never mint executables."""
    if uniform:
        return _run_gang_uniform_jit, (cfg, na, carry, xs, table, n_actual,
                                       needed, L, K, J)
    return _run_gang_scan_fn, (cfg, na, carry, xs, table, wt, needed, dom,
                               statics, w_contig)
