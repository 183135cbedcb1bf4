"""The batched scheduling program: one jitted lax.scan over the pod axis.

This is the TPU replacement for the reference's hot loops (SURVEY §3.2):
`findNodesThatPassFilters` (schedule_one.go:630, Parallelizer over nodes) and
`prioritizeNodes`' three score phases (runtime/framework.go:1286-1390) become
node-axis vectorized kernels, and `ScheduleOne`'s serial pod loop becomes the
scan — sequential in pods (exact greedy parity: each placement updates the
carried `used`/`npods`/`ports` before the next pod sees them), parallel in
nodes.

Filter kernels (all → bool[N]):
  fit          noderesources/fit.go:649-738 (per-column compare, pod count)
  node_name    nodename/node_name.go (interned id equality)
  unschedulable node_unschedulable.go (+ toleration escape)
  taints       tainttoleration (NoSchedule/NoExecute untolerated)
  selector     nodeaffinity + spec.nodeSelector (compiled id tables)
  ports        nodeports (interned (proto,port) id collision)

Score kernels (int64, reference formulas + normalization exactly):
  least_allocated   least_allocated.go:30-60 (int division, NonZeroRequested)
  balanced          balanced_allocation.go:195-237 (std of fractions)
  taint_score       PreferNoSchedule count, DefaultNormalize reverse
  node_affinity     preferred term weights, DefaultNormalize

Tie-break: masked argmax picks the FIRST max index — the deterministic
tie-break the host oracle uses (runtime.py), a legal member of the Go score
heap's randomized argmax set (schedule_one.go:940-944).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from ..ops.collectives import pmax
from ..ops.groups import (DryRunSpread, GroupCarry, GroupFamilies, GroupsDev,
                          group_mask, group_scores, group_update, wave_fold)
from ..state.batch import (OP_DOES_NOT_EXIST, OP_EXISTS, OP_GT, OP_IN,
                           OP_LT, OP_NOT_IN, TOL_EQUAL, TOL_EXISTS)
from ..state.tensorize import (EFFECT_NO_EXECUTE, EFFECT_NO_SCHEDULE,
                               EFFECT_PREFER_NO_SCHEDULE, NodeArrays)
# every public jit entry below is a `device_entry` (perf/ledger.py): its
# dispatches are staged through the sanitizer rails and measured by the
# compile ledger under its kernel label
from ..perf.ledger import GLOBAL as LEDGER, device_entry

MAX_SCORE = 100


class ScoreConfig(NamedTuple):
    """Static per-profile scoring configuration (hashable → jit cache key)."""

    score_cols: tuple[int, ...] = (0, 1)        # resource columns to score
    col_weights: tuple[int, ...] = (1, 1)       # per-column weights
    col_nonzero: tuple[bool, ...] = (True, True)  # use NonZeroRequested path
    nonzero_slot: tuple[int, ...] = (0, 1)      # index into nonzero arrays
    w_fit: int = 1
    w_balanced: int = 1
    w_taint: int = 3
    w_node_affinity: int = 2
    w_spread: int = 2                           # PodTopologySpread weight
    w_ipa: int = 2                              # InterPodAffinity weight
    w_image: int = 1                            # ImageLocality weight
    strategy: str = "LeastAllocated"            # or MostAllocated


class SigCache(NamedTuple):
    """Per-signature cached evaluation (KEP-5598 opportunistic batching,
    reference runtime/batch.go:33-240, generalized): consecutive pods with an
    identical device row reuse the carry-independent kernels — only the fit
    mask and fit-derived scores of the single node touched by the previous
    placement are recomputed. sig 0 never matches."""

    sig: jnp.ndarray          # i32 scalar — signature these vectors belong to
    static_mask: jnp.ndarray  # bool [N] — nodename/unsched/taints/selector/ports
    taint_raw: jnp.ndarray    # i64 [N] — PreferNoSchedule counts (pre-normalize)
    na_raw: jnp.ndarray       # i64 [N] — preferred-affinity weights (pre-normalize)
    s_img: jnp.ndarray        # i64 [N] — ImageLocality score (carry-independent)
    fit_ok: jnp.ndarray       # bool [N]
    s_fit: jnp.ndarray        # i64 [N]
    s_bal: jnp.ndarray        # i64 [N]


class Carry(NamedTuple):
    used: jnp.ndarray          # i64 [N, R]
    nonzero_used: jnp.ndarray  # i64 [N, 2]
    npods: jnp.ndarray         # i32 [N]
    ports: jnp.ndarray         # i32 [N, P]
    cache: SigCache
    # PodTopologySpread / InterPodAffinity counts (None when the batch and
    # cluster carry no group constraints — the lean program compiles without
    # any group compute)
    groups: GroupCarry | None = None


# ---------------------------------------------------------------------------
# filter kernels (operate on full node axis)


def fit_mask(cap, used, npods, allowed_pods, req):
    pods_ok = npods + 1 <= allowed_pods
    cols_ok = jnp.all((req[None, :] == 0) | (used + req[None, :] <= cap), axis=1)
    return pods_ok & cols_ok


def tolerates(tol_key, tol_val, tol_eff, tol_op, taint_key, taint_val, taint_eff):
    """toleration.go:29-56 broadcast: [T_n, TT] → does toleration tt cover
    taint tn. Empty toleration key (id 0) matches all keys; empty effect
    (0) matches all effects; Exists ignores value."""
    key_ok = (tol_key[None, :] == 0) | (tol_key[None, :] == taint_key[:, None])
    eff_ok = (tol_eff[None, :] == 0) | (tol_eff[None, :] == taint_eff[:, None])
    val_ok = (tol_op[None, :] == TOL_EXISTS) | (tol_val[None, :] == taint_val[:, None])
    return (tol_op[None, :] != 0) & key_ok & eff_ok & val_ok


def taint_filter_mask(na: NodeArrays, pod):
    """No untolerated NoSchedule/NoExecute taint."""
    # [N, T_n, TT]
    tol = jax.vmap(tolerates, in_axes=(None, None, None, None, 0, 0, 0))(
        pod.tol_key, pod.tol_val, pod.tol_eff, pod.tol_op,
        na.taint_key, na.taint_val, na.taint_eff)
    tolerated = jnp.any(tol, axis=2)                       # [N, T_n]
    hard = ((na.taint_eff == EFFECT_NO_SCHEDULE)
            | (na.taint_eff == EFFECT_NO_EXECUTE))
    return ~jnp.any(hard & ~tolerated, axis=1)


def taint_prefer_count(na: NodeArrays, pod):
    """tainttoleration Score: count untolerated PreferNoSchedule taints;
    only tolerations with empty or PreferNoSchedule effect participate
    (taint_toleration.go getAllTolerationPreferNoSchedule)."""
    prefer_tol_op = jnp.where(
        (pod.tol_eff == 0) | (pod.tol_eff == EFFECT_PREFER_NO_SCHEDULE),
        pod.tol_op, 0)
    tol = jax.vmap(tolerates, in_axes=(None, None, None, None, 0, 0, 0))(
        pod.tol_key, pod.tol_val, pod.tol_eff, prefer_tol_op,
        na.taint_key, na.taint_val, na.taint_eff)
    tolerated = jnp.any(tol, axis=2)
    prefer = na.taint_eff == EFFECT_PREFER_NO_SCHEDULE
    return jnp.sum(prefer & ~tolerated, axis=1).astype(jnp.int64)


def _requirement_ok(label_key, label_kv, label_num, key, op, num, vals):
    """One selector requirement vs one node's label rows.
    label_*: [L]; vals: [V] → bool."""
    key_hit = (label_key == key) & (key != 0)
    key_present = jnp.any(key_hit)
    kv_match = jnp.any((label_kv[:, None] == vals[None, :]) & (vals[None, :] != 0))
    # numeric value of `key` on this node (NON_NUMERIC if absent/non-int)
    numeric = jnp.max(jnp.where(key_hit, label_num, jnp.iinfo(jnp.int64).min))
    has_numeric = key_present & (numeric != jnp.iinfo(jnp.int64).min)
    return jnp.select(
        [op == OP_IN, op == OP_NOT_IN, op == OP_EXISTS, op == OP_DOES_NOT_EXIST,
         op == OP_GT, op == OP_LT],
        [kv_match, ~kv_match, key_present, ~key_present,
         has_numeric & (numeric > num), has_numeric & (numeric < num)],
        default=jnp.array(True),  # op 0 = padding
    )


def _term_ok(label_key, label_kv, label_num, keys, ops, nums, vals):
    """[Q] requirements ANDed."""
    f = jax.vmap(_requirement_ok, in_axes=(None, None, None, 0, 0, 0, 0))
    return jnp.all(f(label_key, label_kv, label_num, keys, ops, nums, vals))


def selector_mask(na: NodeArrays, pod):
    """spec.nodeSelector conjuncts AND required nodeAffinity terms (ORed) —
    component-helpers nodeaffinity.GetRequiredNodeAffinity semantics."""
    # nodeSelector: every (key, kv) must be present
    def one_node_sel(label_kv):
        present = (pod.ns_sel_val[:, None] == label_kv[None, :]).any(axis=1)
        return jnp.all((pod.ns_sel_val == 0) | present)
    sel_ok = jax.vmap(one_node_sel)(na.label_kv)

    def one_node_aff(label_key, label_kv, label_num):
        terms = jax.vmap(_term_ok, in_axes=(None, None, None, 0, 0, 0, 0))(
            label_key, label_kv, label_num,
            pod.aff_key, pod.aff_op, pod.aff_num, pod.aff_val)
        return jnp.any(terms & pod.aff_term_valid)
    aff_ok = jnp.where(pod.aff_has,
                       jax.vmap(one_node_aff)(na.label_key, na.label_kv, na.label_num),
                       True)
    return sel_ok & aff_ok


def preferred_affinity_score(na: NodeArrays, pod):
    """nodeaffinity Score: Σ weight over matching preferred terms."""
    def one_node(label_key, label_kv, label_num):
        match = jax.vmap(_term_ok, in_axes=(None, None, None, 0, 0, 0, 0))(
            label_key, label_kv, label_num,
            pod.pref_key, pod.pref_op, pod.pref_num, pod.pref_val)
        return jnp.sum(jnp.where(match, pod.pref_weight, 0))
    return jax.vmap(one_node)(na.label_key, na.label_kv, na.label_num)


def ports_mask(ports, pod_port_ids):
    """nodeports: no interned (proto,port) id collision. Also requires
    enough free row slots to record the pod's ports — without this a
    placement could silently drop port bookkeeping and let a later pod in
    the batch double-book the port (divergence from the host cache)."""
    collide = (ports[:, :, None] == pod_port_ids[None, None, :]) & (
        pod_port_ids[None, None, :] != 0)
    ok = ~jnp.any(collide, axis=(1, 2))
    free = jnp.sum(ports == 0, axis=1)
    needed = jnp.sum(pod_port_ids != 0)
    return ok & (free >= needed)


# ---------------------------------------------------------------------------
# score kernels


# single source of truth for the reference thresholds: the host plugin
from ..plugins.imagelocality import (MAX_CONTAINER_THRESHOLD as
                                     IMG_MAX_CONTAINER_THRESHOLD,
                                     MIN_THRESHOLD as IMG_MIN_THRESHOLD)


def image_locality_score(na: NodeArrays, pod, axis=None):
    """image_locality.go:95-131 on device: per container image, the node's
    stored size scaled by the image's cluster spread (numNodes/totalNodes,
    float64 then truncated — the host plugin's exact arithmetic), summed,
    clamped to [minThreshold, containers·maxContainerThreshold], mapped to
    [0, 100]. Carry-independent: node images are static per snapshot."""
    # presence[N, IC]: does node n hold image c; sizes via the same match
    match = (na.image_id[:, :, None] == pod.img_ids[None, None, :]) & (
        pod.img_ids[None, None, :] != 0)                     # [N, I, IC]
    size_c = jnp.sum(jnp.where(match, na.image_size[:, :, None], 0),
                     axis=1)                                 # [N, IC]
    present_c = jnp.any(match, axis=1)                       # [N, IC]
    # numNodesWithImage over valid nodes; total = schedulable node count —
    # GLOBAL across shards (the spread ratio is a cluster-wide quantity)
    num_with = jnp.sum(present_c & na.valid[:, None], axis=0)  # [IC]
    total = jnp.sum(na.valid)
    if axis is not None:
        num_with = lax.psum(num_with, axis)
        total = lax.psum(total, axis)
    total = jnp.maximum(total, 1)
    spread = num_with.astype(jnp.float64) / total.astype(jnp.float64)
    scaled = (size_c.astype(jnp.float64) * spread[None, :]).astype(jnp.int64)
    sum_scores = jnp.sum(scaled, axis=1)                     # [N]
    nc = jnp.maximum(pod.img_containers, 1).astype(jnp.int64)
    max_thr = IMG_MAX_CONTAINER_THRESHOLD * nc
    clamped = jnp.clip(sum_scores, IMG_MIN_THRESHOLD, max_thr)
    score = (MAX_SCORE * (clamped - IMG_MIN_THRESHOLD)
             // jnp.maximum(max_thr - IMG_MIN_THRESHOLD, 1))
    return jnp.where(pod.img_containers > 0, score, 0)


def least_allocated(cfg: ScoreConfig, cap, used_cols):
    """least_allocated.go:30-60 exact int64 arithmetic, per node.
    cap/used_cols: [N, C] for the configured score columns. Padding rows
    score 0 harmlessly; feasibility masking excludes them from argmax."""
    w = jnp.array(cfg.col_weights, jnp.int64)
    col_ok = cap > 0
    if cfg.strategy == "MostAllocated":
        raw = jnp.where((cap == 0) | (used_cols > cap), 0,
                        used_cols * MAX_SCORE // jnp.maximum(cap, 1))
    else:
        raw = jnp.where((cap == 0) | (used_cols > cap), 0,
                        (cap - used_cols) * MAX_SCORE // jnp.maximum(cap, 1))
    score_sum = jnp.sum(jnp.where(col_ok, raw * w[None, :], 0), axis=1)
    w_sum = jnp.sum(jnp.where(col_ok, w[None, :], 0), axis=1)
    return jnp.where(w_sum > 0, score_sum // jnp.maximum(w_sum, 1), 0)


def balanced_allocation(cap, used_cols):
    """balanced_allocation.go:195-237: 100·(1−std of utilization fractions)."""
    col_ok = cap > 0
    frac = jnp.where(col_ok, jnp.minimum(used_cols / jnp.maximum(cap, 1), 1.0), 0.0)
    cnt = jnp.sum(col_ok, axis=1)
    total = jnp.sum(frac, axis=1)
    mean = total / jnp.maximum(cnt, 1)
    var = jnp.sum(jnp.where(col_ok, (frac - mean[:, None]) ** 2, 0.0), axis=1) / jnp.maximum(cnt, 1)
    # population std; for the 2-column case this equals the reference's
    # |f0−f1|/2 special case (balanced_allocation.go:224-227) exactly
    std = jnp.sqrt(var)
    # int truncation with epsilon guard against float error at exact integers
    return jnp.floor((1.0 - std) * MAX_SCORE + 1e-9).astype(jnp.int64)


def default_normalize(scores, feasible, reverse: bool, axis: str | None = None):
    """plugins/helper DefaultNormalizeScore over the feasible set.

    `axis`: mesh axis name when the node dimension is sharded — the max must
    be GLOBAL across shards or normalization denominators diverge per device
    (parallel/sharding.py)."""
    maxc = jnp.max(jnp.where(feasible, scores, 0))
    if axis is not None:
        maxc = pmax(maxc, axis)
    scaled = jnp.where(maxc > 0, scores * MAX_SCORE // jnp.maximum(maxc, 1),
                       jnp.where(reverse, MAX_SCORE, scores))
    if reverse:
        scaled = jnp.where(maxc > 0, MAX_SCORE - scaled, scaled)
    return scaled


# ---------------------------------------------------------------------------
# the scan


class PodTableDev(NamedTuple):
    """Device copy of state.batch.PodTable ([U, ...], U = distinct sigs)."""

    req: jnp.ndarray
    nonzero_req: jnp.ndarray
    node_name_id: jnp.ndarray
    tol_key: jnp.ndarray
    tol_val: jnp.ndarray
    tol_eff: jnp.ndarray
    tol_op: jnp.ndarray
    tolerates_unsched: jnp.ndarray
    ns_sel_val: jnp.ndarray
    aff_has: jnp.ndarray
    aff_term_valid: jnp.ndarray
    aff_key: jnp.ndarray
    aff_op: jnp.ndarray
    aff_num: jnp.ndarray
    aff_val: jnp.ndarray
    pref_weight: jnp.ndarray
    pref_key: jnp.ndarray
    pref_op: jnp.ndarray
    pref_num: jnp.ndarray
    pref_val: jnp.ndarray
    port_ids: jnp.ndarray
    skip_balanced: jnp.ndarray
    img_ids: jnp.ndarray
    img_containers: jnp.ndarray


class PodXs(NamedTuple):
    """Per-pod scan xs: the only O(B) upload per batch."""

    valid: jnp.ndarray   # bool [B]
    sig: jnp.ndarray     # i32 [B]
    tidx: jnp.ndarray    # i32 [B] — row into PodTableDev
    # node row of the pod's OWN pending nomination (-1 = none): the overlay
    # must exclude the pod's own nominated resources exactly like the
    # reference two-pass skips the pod's own entry
    # (runtime/framework.go:1183). Nominated pods carry sig 0 so the
    # signature cache neither serves nor stores their per-pod fit.
    nom_idx: jnp.ndarray = None


class PodRow(NamedTuple):
    """One pod's view inside the scan step: table row + per-pod scalars."""

    valid: jnp.ndarray
    sig: jnp.ndarray
    req: jnp.ndarray
    nonzero_req: jnp.ndarray
    node_name_id: jnp.ndarray
    tol_key: jnp.ndarray
    tol_val: jnp.ndarray
    tol_eff: jnp.ndarray
    tol_op: jnp.ndarray
    tolerates_unsched: jnp.ndarray
    ns_sel_val: jnp.ndarray
    aff_has: jnp.ndarray
    aff_term_valid: jnp.ndarray
    aff_key: jnp.ndarray
    aff_op: jnp.ndarray
    aff_num: jnp.ndarray
    aff_val: jnp.ndarray
    pref_weight: jnp.ndarray
    pref_key: jnp.ndarray
    pref_op: jnp.ndarray
    pref_num: jnp.ndarray
    pref_val: jnp.ndarray
    port_ids: jnp.ndarray
    skip_balanced: jnp.ndarray
    img_ids: jnp.ndarray
    img_containers: jnp.ndarray
    nom_idx: jnp.ndarray = None   # see PodXs.nom_idx


def _gather_row(table: PodTableDev, x) -> PodRow:
    fields = {name: getattr(table, name)[x.tidx]
              for name in PodTableDev._fields}
    return PodRow(valid=x.valid, sig=x.sig, nom_idx=x.nom_idx, **fields)


def table_from_batch(batch) -> PodTableDev:
    """PodBatch → device signature table."""
    table = PodTableDev(*(jnp.asarray(getattr(batch.table, f))
                          for f in PodTableDev._fields))
    LEDGER.note_h2d_tree("host_cache", table)
    return table


def pod_rows_from_batch(batch) -> tuple[PodXs, PodTableDev]:
    """PodBatch → (per-pod xs, device signature table)."""
    xs = PodXs(valid=jnp.asarray(batch.valid), sig=jnp.asarray(batch.sig),
               tidx=jnp.asarray(batch.tidx))
    return xs, table_from_batch(batch)


def _fit_scores(cfg: ScoreConfig, na: NodeArrays, carry: Carry, pod: PodRow):
    """LeastAllocated + BalancedAllocation over all nodes → ([N], [N])."""
    cols = jnp.array(cfg.score_cols, jnp.int32)
    cap_cols = na.cap[:, cols]                        # [N, C]
    nz = jnp.array(cfg.col_nonzero)
    slots = jnp.array(cfg.nonzero_slot, jnp.int32)
    used_nonzero = carry.nonzero_used[:, slots] + pod.nonzero_req[slots][None, :]
    used_plain = carry.used[:, cols] + pod.req[cols][None, :]
    used_cols = jnp.where(nz[None, :], used_nonzero, used_plain)
    s_fit = least_allocated(cfg, cap_cols, used_cols)
    used_bal = carry.used[:, cols] + pod.req[cols][None, :]
    s_bal = jnp.where(pod.skip_balanced, 0, balanced_allocation(cap_cols, used_bal))
    return s_fit, s_bal


def _slow_parts(cfg: ScoreConfig, na: NodeArrays, carry: Carry, pod: PodRow,
                axis: str | None = None, overlay=None):
    """The full kernel set: everything SigCache caches, freshly computed.
    ports_mask folds into static_mask — pods eligible for the fast path
    carry no host ports (BatchBuilder gives them sig 0 otherwise), so the
    cached value is vacuously true whenever it can be reused.

    `overlay` = (ovl_used [N,R], ovl_npods [N]) or None: nominated
    (preemptor) pods' resources folded into the FIT check only — the
    with-nominated pass of RunFilterPluginsWithNominatedPods
    (runtime/framework.go:1158); scoring stays overlay-free exactly like
    the reference's prioritizeNodes, which never sees nominated pods."""
    m = na.valid
    m &= (pod.node_name_id == 0) | (na.name_id == pod.node_name_id)
    m &= ~na.unschedulable | pod.tolerates_unsched
    m &= taint_filter_mask(na, pod)
    m &= selector_mask(na, pod)
    m &= ports_mask(carry.ports, pod.port_ids)
    taint_raw = taint_prefer_count(na, pod)
    na_raw = preferred_affinity_score(na, pod)
    s_img = image_locality_score(na, pod, axis=axis)
    if overlay is None:
        fit_used, fit_npods = carry.used, carry.npods
    else:
        # NOTE: no per-pod self-exclusion here — the cached fit_ok must be
        # signature-pure so same-sig pods with different nominations share
        # it; _eval_pod applies the one-row exclusion delta on top
        fit_used = carry.used + overlay[0]
        fit_npods = carry.npods + overlay[1]
    fit_ok = fit_mask(na.cap, fit_used, fit_npods, na.allowed_pods, pod.req)
    s_fit, s_bal = _fit_scores(cfg, na, carry, pod)
    return m, taint_raw, na_raw, s_img, fit_ok, s_fit, s_bal


def _row_refresh(cfg: ScoreConfig, na: NodeArrays, c2: Carry, pod: PodRow,
                 best: jnp.ndarray, gate: jnp.ndarray, cache: SigCache,
                 overlay=None) -> SigCache:
    """Recompute fit_ok/s_fit/s_bal for the single row the placement touched
    (everything else in the cache is carry-independent)."""
    cols = jnp.array(cfg.score_cols, jnp.int32)
    nz = jnp.array(cfg.col_nonzero)
    slots = jnp.array(cfg.nonzero_slot, jnp.int32)
    cap_row = na.cap[best]
    used_row = c2.used[best]
    fit_used_row = used_row if overlay is None else used_row + overlay[0][best]
    fit_npods = (c2.npods[best] if overlay is None
                 else c2.npods[best] + overlay[1][best])
    fit_ok_b = ((fit_npods + 1 <= na.allowed_pods[best])
                & jnp.all((pod.req == 0) | (fit_used_row + pod.req <= cap_row)))
    cap_r = cap_row[cols][None, :]
    used_nz_r = c2.nonzero_used[best][slots] + pod.nonzero_req[slots]
    used_pl_r = used_row[cols] + pod.req[cols]
    used_cols_r = jnp.where(nz, used_nz_r, used_pl_r)[None, :]
    s_fit_b = least_allocated(cfg, cap_r, used_cols_r)[0]
    s_bal_b = jnp.where(pod.skip_balanced, 0,
                        balanced_allocation(cap_r, used_pl_r[None, :])[0])
    return SigCache(
        sig=pod.sig,
        static_mask=cache.static_mask,
        taint_raw=cache.taint_raw,
        na_raw=cache.na_raw,
        s_img=cache.s_img,
        fit_ok=cache.fit_ok.at[best].set(
            jnp.where(gate, fit_ok_b, cache.fit_ok[best])),
        s_fit=cache.s_fit.at[best].set(
            jnp.where(gate, s_fit_b, cache.s_fit[best])),
        s_bal=cache.s_bal.at[best].set(
            jnp.where(gate, s_bal_b, cache.s_bal[best])),
    )


def _eval_pod(cfg: ScoreConfig, na: NodeArrays, carry: Carry, pod: PodRow,
              axis: str | None = None, groups: GroupsDev | None = None,
              tidx=None, n_global: int | None = None,
              fam: GroupFamilies | None = None, overlay=None):
    """Feasibility + total score for one pod over all nodes → (mask, score,
    parts). Consults the signature cache: a pod whose sig matches the carry's
    reuses every carry-independent kernel (the expensive ones). Group kernels
    (spread/inter-pod affinity) are carry-COUPLED — every placement can move
    their counts for every signature — so they always evaluate live and are
    never cached. `axis` names the mesh axis when `na`/`carry` hold one node
    shard."""
    cache = carry.cache
    use_fast = (pod.sig != 0) & (pod.sig == cache.sig)
    m, taint_raw, na_raw, s_img, fit_ok, s_fit, s_bal = lax.cond(
        use_fast,
        lambda: (cache.static_mask, cache.taint_raw, cache.na_raw,
                 cache.s_img, cache.fit_ok, cache.s_fit, cache.s_bal),
        lambda: _slow_parts(cfg, na, carry, pod, axis=axis, overlay=overlay))

    fit_ok_eff = fit_ok
    if overlay is not None and pod.nom_idx is not None:
        # per-pod self-exclusion delta (framework.go:1183 skips the pod's
        # own nomination): recompute fit at the ONE row the pod's own
        # nomination occupies, minus its own contribution. Applied to the
        # EFFECTIVE mask only — the cached fit_ok stays signature-pure so
        # same-sig pods with different nominations share the fast path.
        safe = jnp.maximum(pod.nom_idx, 0)
        own_used = carry.used[safe] + overlay[0][safe] - pod.req
        own_npods = carry.npods[safe] + overlay[1][safe] - 1
        own_fit = ((own_npods + 1 <= na.allowed_pods[safe])
                   & jnp.all((pod.req == 0)
                             | (own_used + pod.req <= na.cap[safe])))
        fit_ok_eff = fit_ok.at[safe].set(
            jnp.where(pod.nom_idx >= 0, own_fit, fit_ok[safe]))

    feasible = m & fit_ok_eff
    if groups is not None:
        # fold in BEFORE normalization: the host runtime normalizes over the
        # fully-filtered node list, so a group-filtered node must not set the
        # normalization max (runtime/framework.go:1286-1390 semantics)
        feasible &= group_mask(groups, carry.groups, tidx, axis=axis,
                               fam=fam)
    s_taint = default_normalize(taint_raw, feasible, reverse=True, axis=axis)
    s_na = default_normalize(na_raw, feasible, reverse=False, axis=axis)
    total = (cfg.w_fit * s_fit + cfg.w_balanced * s_bal
             + cfg.w_taint * s_taint + cfg.w_node_affinity * s_na
             + cfg.w_image * s_img)
    if groups is not None:
        total = total + group_scores(cfg.w_spread, cfg.w_ipa, groups,
                                     carry.groups, tidx, feasible,
                                     axis=axis, n_global=n_global, fam=fam)
    parts = SigCache(sig=pod.sig, static_mask=m, taint_raw=taint_raw,
                     na_raw=na_raw, s_img=s_img, fit_ok=fit_ok, s_fit=s_fit,
                     s_bal=s_bal)
    return feasible, total, parts


# ---------------------------------------------------------------------------
# failure diagnosis reduction
#
# The host oracle recovers "why did this pod fail" by replaying every filter
# plugin over every node in Python (find_nodes_that_pass_filters). The same
# information is already present in the device filter masks; this reduction
# attributes every rejected node to its FIRST failing filter in the host
# plugin order — the sequential-filter semantics of run_filter_plugins,
# where a node's status comes from the first plugin that rejects it — plus
# the per-resource fit detail the NodeResourcesFit reasons need.

# reason slots (host filter order; plugins the device kernels model)
DIAG_FEASIBLE = 0
DIAG_INVALID = -1                 # padding / freed node row
DIAG_NODE_UNSCHEDULABLE = 1
DIAG_NODE_NAME = 2
DIAG_TAINT = 3
DIAG_NODE_AFFINITY = 4
DIAG_PORTS = 5
DIAG_FIT = 6
DIAG_SPREAD_LABEL = 7             # missing topology key (unresolvable)
DIAG_SPREAD_SKEW = 8
DIAG_IPA_AFFINITY = 9
DIAG_IPA_ANTI = 10
DIAG_IPA_EXISTING_ANTI = 11


def _diagnose_masks(na: NodeArrays, pod: PodRow, gd, gc, tidx, fam):
    """Per-node first-failing-filter slot + fit detail, all [N]-shaped."""
    from ..ops.groups import group_reason_masks

    unsched_ok = ~na.unschedulable | pod.tolerates_unsched
    name_ok = (pod.node_name_id == 0) | (na.name_id == pod.node_name_id)
    taint_ok = taint_filter_mask(na, pod)
    sel_ok = selector_mask(na, pod)
    ports_ok = ports_mask(na.ports, pod.port_ids)
    pods_fail = na.npods + 1 > na.allowed_pods
    cols_fail = (pod.req[None, :] != 0) & (na.used + pod.req[None, :]
                                           > na.cap)           # [N, R]
    fit_ok = ~pods_fail & ~jnp.any(cols_fail, axis=1)
    n = na.valid.shape[0]
    false = jnp.zeros((n,), bool)
    if gd is not None:
        spr_missing, spr_skew, aff_f, anti_f, exist_f = group_reason_masks(
            gd, gc, tidx, fam)
    else:
        spr_missing = spr_skew = aff_f = anti_f = exist_f = false
    slot = jnp.select(
        [~na.valid,
         ~unsched_ok, ~name_ok, ~taint_ok, ~sel_ok, ~ports_ok, ~fit_ok,
         spr_missing, spr_skew, aff_f, anti_f, exist_f],
        [jnp.int32(DIAG_INVALID), jnp.int32(DIAG_NODE_UNSCHEDULABLE),
         jnp.int32(DIAG_NODE_NAME), jnp.int32(DIAG_TAINT),
         jnp.int32(DIAG_NODE_AFFINITY), jnp.int32(DIAG_PORTS),
         jnp.int32(DIAG_FIT), jnp.int32(DIAG_SPREAD_LABEL),
         jnp.int32(DIAG_SPREAD_SKEW), jnp.int32(DIAG_IPA_AFFINITY),
         jnp.int32(DIAG_IPA_ANTI), jnp.int32(DIAG_IPA_EXISTING_ANTI)],
        default=jnp.int32(DIAG_FEASIBLE))
    return slot, pods_fail, cols_fail


@functools.partial(jax.jit, static_argnames=("fam",))
def _diagnose_groups(na: NodeArrays, table: PodTableDev, tidx, gd, gc, fam):
    pod = _gather_row(table, PodXs(valid=jnp.bool_(True), sig=jnp.int32(0),
                                   tidx=tidx))
    return _diagnose_masks(na, pod, gd, gc, tidx, fam)


@jax.jit
def _diagnose_lean(na: NodeArrays, table: PodTableDev, tidx):
    pod = _gather_row(table, PodXs(valid=jnp.bool_(True), sig=jnp.int32(0),
                                   tidx=tidx))
    return _diagnose_masks(na, pod, None, None, tidx, None)


@device_entry("diagnose", cost=(16.0, 2.0))
def diagnose_row(na: NodeArrays, table: PodTableDev, tidx: int,
                 gd=None, gc=None, fam=None):
    """Reduce the filter masks of signature row `tidx` against node state
    `na` (used/npods/ports = the post-commit truth) into
    (slot i32 [N], fit_pods_fail bool [N], fit_cols_fail bool [N, R]):
    `slot` holds each node's first failing filter (DIAG_*), the fit arrays
    carry the per-reason detail for DIAG_FIT nodes ("Too many pods" /
    per-column Insufficient)."""
    if gd is not None:
        return _diagnose_groups, (na, table, jnp.int32(tidx), gd, gc, fam)
    return _diagnose_lean, (na, table, jnp.int32(tidx))


# ---------------------------------------------------------------------------
# decision provenance: per-plugin score decomposition (ISSUE 10)
#
# diagnose_row answers "why did every node REJECT this pod"; explain_row
# answers the complement — "why did the winning node WIN": the per-plugin
# score columns of the top-k feasible nodes, evaluated through the exact
# scan-step formula (_eval_pod), so the reported winner and margin are
# bit-identical to the argmax the dispatched program took at the same
# carry state.

# explain column order (host rendering maps these to plugin names):
# weighted Fit, BalancedAllocation, TaintToleration, NodeAffinity,
# ImageLocality, and the combined group contribution
# (PodTopologySpread + InterPodAffinity — group_scores returns their sum)
EXPLAIN_COLUMNS = ("NodeResourcesFit", "NodeResourcesBalancedAllocation",
                   "TaintToleration", "NodeAffinity", "ImageLocality",
                   "PodTopologySpread+InterPodAffinity")


def _explain_masks(cfg: ScoreConfig, na: NodeArrays, carry: Carry, tidx,
                   k: int, table: PodTableDev, groups, fam):
    pod = _gather_row(table, PodXs(valid=jnp.bool_(True),
                                   sig=jnp.int32(0), tidx=tidx))
    feasible, total, parts = _eval_pod(cfg, na, carry, pod, groups=groups,
                                       tidx=tidx, fam=fam)
    masked = jnp.where(feasible, total, jnp.int64(-1))
    s_taint = default_normalize(parts.taint_raw, feasible, reverse=True)
    s_na = default_normalize(parts.na_raw, feasible, reverse=False)
    base = (cfg.w_fit * parts.s_fit + cfg.w_balanced * parts.s_bal
            + cfg.w_taint * s_taint + cfg.w_node_affinity * s_na
            + cfg.w_image * parts.s_img)
    cols = jnp.stack([cfg.w_fit * parts.s_fit,
                      cfg.w_balanced * parts.s_bal,
                      cfg.w_taint * s_taint,
                      cfg.w_node_affinity * s_na,
                      cfg.w_image * parts.s_img,
                      total - base], axis=1)            # [N, 6]
    # scores bounded by 100·Σweights: the int32 top_k (ties → lowest
    # index) reproduces the scan's first-max argmax tie-break exactly
    _, idx = lax.top_k(masked.astype(jnp.int32), k)
    idx = idx.astype(jnp.int32)
    return idx, masked[idx], cols[idx], jnp.sum(feasible).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("cfg", "k", "fam"))
def _explain_groups(cfg: ScoreConfig, na: NodeArrays, carry: Carry,
                    table: PodTableDev, tidx, k: int, gd, fam):
    return _explain_masks(cfg, na, carry, tidx, k, table, gd, fam)


@functools.partial(jax.jit, static_argnames=("cfg", "k"))
def _explain_lean(cfg: ScoreConfig, na: NodeArrays, carry: Carry,
                  table: PodTableDev, tidx, k: int):
    return _explain_masks(cfg, na, carry, tidx, k, table, None, None)


@device_entry(cost=(16.0, 2.0))
def explain_row(cfg: ScoreConfig, na: NodeArrays, carry: Carry,
                table: PodTableDev, tidx: int, k: int = 8, gd=None,
                fam=None):
    """Score decomposition of signature row `tidx` against `carry`:
    (topk_idx i32 [k], topk_total i64 [k] (-1 = infeasible slot),
    topk_cols i64 [k, 6] per-plugin weighted contributions in
    EXPLAIN_COLUMNS order, feasible_count i32). topk_idx[0] is
    bit-identical to the argmax the scan/plan program takes for this row
    at this carry (same _eval_pod formula, same tie-break); the win
    margin is topk_total[0] - topk_total[1]."""
    if gd is not None:
        return _explain_groups, (cfg, na, carry, table, jnp.int32(tidx), k,
                                 gd, fam)
    return _explain_lean, (cfg, na, carry, table, jnp.int32(tidx), k)


@jax.jit
def _scatter_rows_jit(dev: NodeArrays, idx, rows: NodeArrays) -> NodeArrays:
    return NodeArrays(*(d.at[idx].set(r) for d, r in zip(dev, rows)))


@device_entry(cost=(2.0, 2.0))
def scatter_rows(dev: NodeArrays, idx, rows: NodeArrays) -> NodeArrays:
    """Generation-diff snapshot upload (ISSUE 9): scatter `rows` (one
    gathered staging row per dirty node, [D, ...] with D a pow2 bucket;
    duplicate indices carry identical rows) into the device-resident
    NodeArrays at `idx` (i32 [D]). The H2D transfer is the rows — O(dirty
    × row width) instead of the O(N × row width) full re-upload.

    Deliberately NON-donating: the previous device copy was handed to
    callers (in-flight drains hold it as `pd.na`; tests hold it across
    mutations), so the entry must materialize fresh output buffers — an
    on-device copy in place of a full host-to-device re-upload."""
    return _scatter_rows_jit, (dev, idx, rows)


@functools.partial(jax.jit, static_argnames=("cfg",))
def _score_probe_jit(cfg: ScoreConfig, na: NodeArrays, carry: Carry,
                     table: PodTableDev, tidx):
    pod = _gather_row(table, PodXs(valid=jnp.bool_(True), sig=jnp.int32(0),
                                   tidx=tidx, nom_idx=jnp.int32(-1)))
    s_fit, s_bal = _fit_scores(cfg, na, carry, pod)
    # re-derive the FLOAT balanced-allocation intermediates: the int
    # floor in balanced_allocation() buries a NaN as garbage, so the
    # probe must observe the std surface before the cast
    cols = jnp.array(cfg.score_cols, jnp.int32)
    cap_cols = na.cap[:, cols]
    used_bal = carry.used[:, cols] + pod.req[cols][None, :]
    col_ok = cap_cols > 0
    frac = jnp.where(col_ok,
                     jnp.minimum(used_bal / jnp.maximum(cap_cols, 1), 1.0),
                     0.0)
    cnt = jnp.sum(col_ok, axis=1)
    mean = jnp.sum(frac, axis=1) / jnp.maximum(cnt, 1)
    var = (jnp.sum(jnp.where(col_ok, (frac - mean[:, None]) ** 2, 0.0),
                   axis=1) / jnp.maximum(cnt, 1))
    std = jnp.sqrt(var)
    total = (cfg.w_fit * s_fit + cfg.w_balanced * s_bal).astype(jnp.float32)
    return total, std.astype(jnp.float32)


@device_entry(cost=(16.0, 2.0))
def score_probe(cfg: ScoreConfig, na: NodeArrays, carry: Carry,
                table: PodTableDev, tidx):
    """Score surface of signature row `tidx` against `carry`, in float:
    (combined fit+balanced score f32 [N], balanced-allocation std f32
    [N]). The sanitizer rails' NaN/inf probe (analysis/rails.py
    check_scores) — one tiny shape-stable kernel per drain."""
    return _score_probe_jit, (cfg, na, carry, table, tidx)


# ---------------------------------------------------------------------------
# cluster analytics: on-device state probe (ISSUE 13)
#
# The carry resident in HBM after every drain IS the cluster state — one
# reduction over it yields the utilization/fragmentation/imbalance
# signals ROADMAP items 2 and 3 consume, at zero extra h2d. Sampled per
# drain by the scheduler, surfaced via /debug/cluster, the
# scheduler_cluster_* gauge families, the flight recorder and the
# telemetry timeline.
#
# Bit-parity contract (tests/test_cluster_probe.py holds this vs a numpy
# oracle): every cross-node reduction is exact int64 arithmetic (masked
# sums, scatter-adds of integers); floats appear only in elementwise
# division/compare, sort, and gather — all deterministic between XLA and
# numpy, so the probe is bit-reproducible.

# per-resource stat columns of the probe's first output, in order
PROBE_STATS = ("p50", "p90", "p99", "max", "mean", "frag", "stranded")
# nearest-rank percentile ranks (idx = floor(q·(m-1) + 0.5) over the m
# nodes advertising the resource)
_PROBE_QS = (0.5, 0.9, 0.99)
# a node whose bottleneck-resource utilization reaches this is "tight":
# its remaining free capacity in OTHER resources counts as stranded
PROBE_TIGHT = 0.95


def _probe_math(cap_in, valid, used_in, npods, dom, ndom: int):
    """The probe reduction on plain arrays (cap i64 [N, R], valid bool
    [N], used i64 [N, R], npods i32 [N], dom i32 [N]) — shared between
    the single-device jit below and the mesh twin
    (parallel/sharding.py cluster_probe_sharded), which all-gathers its
    shards and runs these exact ops so the outputs stay bit-identical."""
    f32, i64 = jnp.float32, jnp.int64
    # a (node, resource) cell participates when the node is valid and
    # advertises capacity for the resource
    part = valid[:, None] & (cap_in > 0)                        # bool [N, R]
    used = jnp.where(part, used_in, 0).astype(i64)              # i64 [N, R]
    cap = jnp.where(part, cap_in, 0).astype(i64)                # i64 [N, R]
    util = jnp.where(part,
                     used.astype(f32) / jnp.maximum(cap, 1).astype(f32),
                     -1.0).astype(f32)                          # f32 [N, R]
    m = jnp.sum(part, axis=0).astype(jnp.int32)                 # i32 [R]
    n_total = util.shape[0]

    # percentiles: non-participants sort to the front as -1, so the m
    # participants occupy [N-m, N) of each sorted column — nearest-rank
    # gather at N-m+idx. idx math in f64 (exact for these magnitudes) so
    # the numpy oracle lands on the identical element.
    srt = jnp.sort(util, axis=0)                                # f32 [N, R]
    mf = m.astype(jnp.float64)
    qcols = []
    for q in _PROBE_QS + (1.0,):
        idx = jnp.floor(q * (mf - 1.0) + 0.5).astype(jnp.int32)
        at = jnp.clip(n_total - m + idx, 0, n_total - 1)
        qcols.append(jnp.where(m > 0,
                               jnp.take_along_axis(srt, at[None, :],
                                                   axis=0)[0], 0.0))

    # aggregate mean utilization: exact int64 sums, one float division
    sum_used = jnp.sum(used, axis=0)                            # i64 [R]
    sum_cap = jnp.sum(cap, axis=0)                              # i64 [R]
    mean = jnp.where(sum_cap > 0,
                     sum_used.astype(f32) / jnp.maximum(sum_cap, 1).astype(f32),
                     0.0)

    # fragmentation: 1 - (largest single free block / total free) — 0
    # when one node could absorb the whole free pool, → 1 as the free
    # capacity shatters into many small holes
    free = cap - used                                           # i64 [N, R]
    tot_free = jnp.sum(free, axis=0)                            # i64 [R]
    max_free = jnp.max(free, axis=0)                            # i64 [R]
    frag = jnp.where(tot_free > 0,
                     1.0 - max_free.astype(f32) /
                     jnp.maximum(tot_free, 1).astype(f32), 0.0)

    # stranded capacity: free units sitting on nodes whose bottleneck
    # resource is already ≥ PROBE_TIGHT utilized — capacity that exists
    # but cannot host a balanced pod
    bottleneck = jnp.max(jnp.where(part, util, 0.0), axis=1)    # f32 [N]
    tight = valid & (bottleneck >= PROBE_TIGHT)                 # bool [N]
    stranded_free = jnp.sum(jnp.where(tight[:, None], free, 0), axis=0)
    stranded = jnp.where(tot_free > 0,
                         stranded_free.astype(f32) /
                         jnp.maximum(tot_free, 1).astype(f32), 0.0)

    per_res = jnp.stack(qcols + [mean, frag, stranded], axis=1)  # f32 [R, 7]

    # topology-domain imbalance over the gang engine's Tesserae dom-id
    # column: per-domain pod density (pods per valid node), exact int64
    # scatter-adds; spread = max - min over populated domains
    dclip = jnp.clip(dom.astype(jnp.int32), 0, ndom - 1)
    dom_pods = jnp.zeros((ndom,), i64).at[dclip].add(
        jnp.where(valid, npods, 0).astype(i64))
    dom_nodes = jnp.zeros((ndom,), i64).at[dclip].add(valid.astype(i64))
    has = dom_nodes > 0
    load = jnp.where(has,
                     dom_pods.astype(f32) /
                     jnp.maximum(dom_nodes, 1).astype(f32), 0.0)
    any_dom = jnp.any(has)
    dmax = jnp.max(jnp.where(has, load, -jnp.inf))
    dmin = jnp.min(jnp.where(has, load, jnp.inf))
    dom_stats = jnp.stack([
        jnp.sum(has).astype(f32),
        jnp.where(any_dom, dmax, 0.0).astype(f32),
        jnp.where(any_dom, dmin, 0.0).astype(f32),
        jnp.where(any_dom, dmax - dmin, 0.0).astype(f32),
    ])                                                          # f32 [4]
    return per_res, dom_stats, jnp.sum(valid).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("ndom",))
def _cluster_probe_jit(na: NodeArrays, carry: Carry, dom, ndom: int):
    return _probe_math(na.cap, na.valid, carry.used, carry.npods, dom,
                       ndom)


@device_entry(cost=(24.0, 2.0))
def cluster_probe(na: NodeArrays, carry: Carry, dom, ndom: int):
    """On-device cluster-state reduction over the resident carry:
    (per_res f32 [R, 7] — PROBE_STATS columns per resource, dom_stats
    f32 [4] — (populated domains, max, min, spread) of per-domain pod
    density, valid_count i32). `dom` is the gang engine's topology
    dom-id column (i32 [N]), `ndom` its static domain count (jit cache
    key — stable per cluster topology). Deliberately NON-donating: the
    carry stays resident for the next drain; the probe only reads it."""
    return _cluster_probe_jit, (na, carry, dom, ndom)


def _apply_assignment(carry: Carry, pod: PodRow, best: jnp.ndarray,
                      assigned: jnp.ndarray) -> Carry:
    onehot = (jnp.arange(carry.npods.shape[0], dtype=jnp.int32) == best) & assigned
    used = carry.used + jnp.where(onehot[:, None], pod.req[None, :], 0)
    nonzero = carry.nonzero_used + jnp.where(onehot[:, None],
                                             pod.nonzero_req[None, :], 0)
    npods = carry.npods + onehot.astype(carry.npods.dtype)
    # place pod port ids into the first free slots of the chosen node's row
    row = carry.ports[best]
    free = row == 0
    rank = jnp.cumsum(free) - 1
    pod_ports = pod.port_ids
    nport = pod_ports.shape[0]
    incoming = jnp.where((rank >= 0) & (rank < nport) & free,
                         pod_ports[jnp.clip(rank, 0, nport - 1)], 0)
    new_row = jnp.where(free, incoming, row)
    ports = jnp.where(
        (onehot[:, None]) & (jnp.any(pod_ports != 0)),
        jnp.broadcast_to(new_row, carry.ports.shape), carry.ports)
    return carry._replace(used=used, nonzero_used=nonzero, npods=npods,
                          ports=ports)


def _run_batch_impl(cfg: ScoreConfig, na: NodeArrays, carry: Carry, pods: PodXs,
                    table: PodTableDev, groups: GroupsDev | None = None,
                    fam: GroupFamilies | None = None, overlay=None):
    """Scan the batch; returns (final carry, assignments int32[B] (-1 = none)).

    `groups` (with `carry.groups`) enables the PodTopologySpread /
    InterPodAffinity kernels; pass None (and carry.groups None) for the lean
    program — the two compile to distinct executables. `fam` (static)
    trims the group kernels to the active constraint families — a
    spread-only batch compiles a program with zero inter-pod-affinity
    compute (≈5-8× per step on TPU); see groups.GroupFamilies."""

    n = na.npods.shape[0]
    consume_nom = overlay is not None and pods.nom_idx is not None

    def step(state, x: PodXs):
        c, ovl = state
        pod = _gather_row(table, x)
        mask, score, parts = _eval_pod(cfg, na, c, pod, groups=groups,
                                       tidx=x.tidx, fam=fam, overlay=ovl)
        masked = jnp.where(mask, score, -1)
        best = jnp.argmax(masked).astype(jnp.int32)
        assigned = (masked[best] >= 0) & pod.valid
        c2 = _apply_assignment(c, pod, best, assigned)
        if consume_nom:
            # a bound pod's nomination is deleted (the commit calls
            # nominator.delete): consume its contribution so later pods in
            # the scan see the same overlay the host sequential path would
            safe = jnp.maximum(pod.nom_idx, 0)
            gate = assigned & (pod.nom_idx >= 0)
            ovl = (ovl[0].at[safe].add(
                       jnp.where(gate, -pod.req, 0)),
                   ovl[1].at[safe].add(
                       jnp.where(gate, -1, 0).astype(ovl[1].dtype)))
        c2 = c2._replace(cache=_row_refresh(cfg, na, c2, pod, best,
                                            assigned, parts,
                                            overlay=ovl))
        if groups is not None:
            c2 = c2._replace(groups=group_update(
                groups, c2.groups, x.tidx,
                pick=lambda arr: arr[..., best],
                is_chosen=jnp.arange(n, dtype=jnp.int32) == best,
                gate=assigned, fam=fam))
        return (c2, ovl), jnp.where(assigned, best, -1)

    (final, _ovl), assignments = lax.scan(step, (carry, overlay), pods)
    return final, assignments


@functools.lru_cache(maxsize=None)
def _run_batch_fn(donate: bool):
    return jax.jit(_run_batch_impl, static_argnames=("cfg", "fam"),
                   donate_argnums=(2,) if donate else ())


@device_entry(donates=2, cost=(48.0, 3.0))
def run_batch(cfg: ScoreConfig, na: NodeArrays, carry: Carry, pods: PodXs,
              table: PodTableDev, groups: GroupsDev | None = None,
              fam: GroupFamilies | None = None, overlay=None):
    """Jitted entry for `_run_batch_impl`; the input carry is DONATED on
    accelerator backends — the scan chain consumes it, so XLA reuses its
    buffers for the output carry instead of copying the resident node
    state on every dispatch. CPU (no donation support) compiles without
    the donation to avoid per-dispatch warnings. Callers that rewind and
    replay (the uniform path's exactness fallback) must therefore never
    reuse a carry already consumed by run_batch — the scheduler keeps
    carry_in only for run_uniform records, which do not donate."""
    return _run_batch_fn, (cfg, na, carry, pods, table, groups, fam,
                           overlay)


def _uniform_matrix(cfg: ScoreConfig, na: NodeArrays, fit_used, fit_npods,
                    score_used, score_nz, cand, pod: PodRow, J: int):
    """The closed-form score matrix [K, J]: entry j = fit + post-placement
    scores of the (j+1)-th run-pod on candidate k. Built column-by-column
    (static unroll) so every device op is a 2-D [K, J] elementwise — no
    [K, J, C] tensors with a tiny minor dim that would waste the 8×128
    vector tiles. Shared by run_uniform (lean path) and the wave merge
    tier (run_wave). Returns (fit_kj, s_fit_kj, s_bal_kj)."""
    K = cand.shape[0]
    j1 = jnp.arange(1, J + 1, dtype=jnp.int64)[None, :]        # [1, J]
    npods_kj = (fit_npods[cand][:, None]
                + j1.astype(fit_npods.dtype))
    fit_kj = npods_kj <= na.allowed_pods[cand][:, None]
    R = na.cap.shape[1]
    for r in range(R):
        cap_r = na.cap[cand, r][:, None]
        used_r = fit_used[cand, r][:, None] + j1 * pod.req[r]
        fit_kj &= (pod.req[r] == 0) | (used_r <= cap_r)

    # LeastAllocated / MostAllocated (least_allocated.go:30-60) unrolled
    # over the score columns; BalancedAllocation via the 2-column closed
    # form |f0−f1|/2 the reference special-cases (balanced_allocation.go
    # :224-227) when C==2, generic otherwise.
    w = cfg.col_weights
    score_sum = jnp.zeros((K, J), jnp.int64)
    w_sum = jnp.zeros((K, J), jnp.int64)
    fracs = []
    bal_cols_ok = []
    for ci, col in enumerate(cfg.score_cols):
        cap_c = na.cap[cand, col][:, None]                      # [K, 1]
        used_pl = score_used[cand, col][:, None] + j1 * pod.req[col]
        if cfg.col_nonzero[ci]:
            slot = cfg.nonzero_slot[ci]
            used_c = (score_nz[cand, slot][:, None]
                      + j1 * pod.nonzero_req[slot])
        else:
            used_c = used_pl
        col_ok = cap_c > 0
        if cfg.strategy == "MostAllocated":
            raw = jnp.where((cap_c == 0) | (used_c > cap_c), 0,
                            used_c * MAX_SCORE // jnp.maximum(cap_c, 1))
        else:
            raw = jnp.where((cap_c == 0) | (used_c > cap_c), 0,
                            (cap_c - used_c) * MAX_SCORE // jnp.maximum(cap_c, 1))
        score_sum += jnp.where(col_ok, raw * w[ci], 0)
        w_sum += jnp.where(col_ok, jnp.int64(w[ci]), 0)
        fracs.append(jnp.where(
            col_ok, jnp.minimum(used_pl / jnp.maximum(cap_c, 1), 1.0), 0.0))
        bal_cols_ok.append(col_ok)
    s_fit_kj = jnp.where(w_sum > 0, score_sum // jnp.maximum(w_sum, 1), 0)
    # same float-op structure as balanced_allocation() — stacked jnp.sum
    # reductions over the column axis, not a sequential Python sum chain —
    # so XLA lowers the same associativity and results stay bit-identical
    # to the scan's (an |f0−f1|/2 shortcut, or a different reduction order,
    # could differ by an ulp at floor boundaries and break parity)
    frac_kjc = jnp.stack(fracs, axis=-1)                 # [K, J, C]
    ok_kjc = jnp.stack(bal_cols_ok, axis=-1) & jnp.ones(
        frac_kjc.shape, bool)
    cnt = jnp.sum(ok_kjc, axis=-1)
    mean = jnp.sum(frac_kjc, axis=-1) / jnp.maximum(cnt, 1)
    var = jnp.sum(jnp.where(ok_kjc, (frac_kjc - mean[..., None]) ** 2, 0.0),
                  axis=-1) / jnp.maximum(cnt, 1)
    std = jnp.sqrt(var)
    s_bal_kj = jnp.where(
        pod.skip_balanced, 0,
        jnp.floor((1.0 - std) * MAX_SCORE + 1e-9).astype(jnp.int64))
    return fit_kj, s_fit_kj, s_bal_kj


def _uniform_core(cfg: ScoreConfig, na: NodeArrays, carry: Carry, x: PodXs,
                  table: PodTableDev, n_actual, L: int, K: int, J: int,
                  overlay=None):
    """Closed-form batch assignment for a run of SAME-SIGNATURE pods — the
    top-k trick of reference runtime/batch.go:97 (sortedNodes.Pop) taken to
    its TPU limit: the whole run becomes ONE top_k instead of L scan steps.

    Why it is exact (and when): during a same-signature run, a placement
    changes node state only on the chosen node, so every node's score is a
    pure function of how many run-pods it already holds: entry (k, j) =
    score of candidate node k after its (j+1)-th placement. The sequential
    greedy (scan) then consumes entries of this matrix in key order
    (score desc, node idx asc, j asc) — the standard k-way-merge argument,
    valid when each node's entry sequence is non-increasing. Therefore the
    exact greedy assignment = the top-L entries of the keyed matrix, and
    pod i gets the node of the i-th entry. Candidates = top-K initial
    scores suffice because the greedy's touched set is a prefix of that
    ordering (a node is first touched only when it is the argmax at its
    initial score).

    The returned `ok` flag is False — caller must discard the result and
    re-run (bigger J, or the scan) — when an exactness precondition fails
    on the actual data:
      * monotonicity: some candidate's masked score sequence increases in j
        (possible for BalancedAllocation on an unbalanced node, or the
        MostAllocated strategy);
      * normalization constancy: TaintToleration / preferred-NodeAffinity
        raw counts are nonzero over the feasible set, so their
        DefaultNormalize denominators could shift as nodes saturate
        mid-run (the scan recomputes them per pod; this path cannot);
      * depth overflow: some candidate received all J of its matrix
        entries (counts == J), meaning the greedy may have wanted even
        more placements there — the truncated matrix diverted them. J is
        a static depth chosen by the caller (≈ a few × L/nodes, TPU-tiled
        tiny); the scheduler escalates J on this failure.

    `x` carries ONE scalar entry (sig/tidx of the run's row); `n_actual` is
    the true run length (≤ L, the padded static length). Requires sig != 0
    (no host ports — the ports carry is untouched) and a lean carry
    (groups is None)."""
    pod = _gather_row(table, x)
    feasible0, total0, parts = _eval_pod(cfg, na, carry, pod,
                                         overlay=overlay)
    masked0 = jnp.where(feasible0, total0, jnp.int64(-1))
    # scores are bounded by 100·Σweights — int32 keys keep TPU sorts cheap
    _, cand = lax.top_k(masked0.astype(jnp.int32), K)  # ties → lowest index
    cand = cand.astype(jnp.int32)

    # static per-node score components (constant under the norm gate)
    s_taint = default_normalize(parts.taint_raw, feasible0, reverse=True)
    s_na = default_normalize(parts.na_raw, feasible0, reverse=False)
    # ImageLocality is unnormalized and carry-independent: safe to fold
    # into the per-candidate constant
    static_add = (cfg.w_taint * s_taint + cfg.w_node_affinity * s_na
                  + cfg.w_image * parts.s_img)[cand]
    static_m = parts.static_mask[cand]
    norm_ok = (jnp.max(jnp.where(feasible0, parts.taint_raw, 0)) == 0) & (
        jnp.max(jnp.where(feasible0, parts.na_raw, 0)) == 0)

    fit_npods = (carry.npods if overlay is None
                 else carry.npods + overlay[1])
    fit_used = carry.used if overlay is None else carry.used + overlay[0]
    fit_kj, s_fit_kj, s_bal_kj = _uniform_matrix(
        cfg, na, fit_used, fit_npods, carry.used, carry.nonzero_used,
        cand, pod, J)

    score_kj = (cfg.w_fit * s_fit_kj + cfg.w_balanced * s_bal_kj
                + static_add[:, None])
    masked_kj = jnp.where(static_m[:, None] & fit_kj, score_kj,
                          jnp.int64(-1))
    mono_ok = jnp.all(masked_kj[:, 1:] <= masked_kj[:, :-1])

    # key = (score desc, node idx asc, j asc); feasible keys ≥ -(M-1),
    # infeasible ≤ -M — strictly separated. int32 when the range allows
    # (score ≤ 100·Σweights): TPU sorts int32 ~2× faster than int64.
    n_nodes = na.cap.shape[0]
    score_max = MAX_SCORE * (cfg.w_fit + cfg.w_balanced + cfg.w_taint
                             + cfg.w_node_affinity + cfg.w_image)
    M = n_nodes * J
    key_dt = jnp.int32 if (score_max + 2) * M < 2 ** 31 else jnp.int64
    ent_id = (cand[:, None].astype(key_dt) * J
              + jnp.arange(J, dtype=key_dt)[None, :])
    flat_key = (masked_kj.astype(key_dt) * key_dt(M) - ent_id).reshape(K * J)
    top_vals, flat_i = lax.top_k(flat_key, L)
    krank = (flat_i // J).astype(jnp.int32)
    node_of = cand[krank]
    sel_ok = (top_vals > -key_dt(M)) & (jnp.arange(L) < n_actual)
    assignments = jnp.where(sel_ok, node_of, -1).astype(jnp.int32)

    counts = jnp.zeros((K,), jnp.int64).at[krank].add(sel_ok.astype(jnp.int64))
    # a candidate that consumed its whole column is truncation-suspect: the
    # exact greedy may have wanted more placements there
    depth_ok = jnp.all(counts < J)
    used = carry.used.at[cand].add(counts[:, None] * pod.req[None, :])
    nonzero = carry.nonzero_used.at[cand].add(
        counts[:, None] * pod.nonzero_req[None, :])
    npods = carry.npods.at[cand].add(counts.astype(carry.npods.dtype))

    # cache refresh: entry j=counts IS the next-pod evaluation for this sig
    ar = jnp.arange(K)
    cnt_i = jnp.minimum(counts, J - 1).astype(jnp.int32)
    new_cache = SigCache(
        sig=pod.sig,
        static_mask=parts.static_mask, taint_raw=parts.taint_raw,
        na_raw=parts.na_raw, s_img=parts.s_img,
        fit_ok=parts.fit_ok.at[cand].set(fit_kj[ar, cnt_i]),
        s_fit=parts.s_fit.at[cand].set(s_fit_kj[ar, cnt_i]),
        s_bal=parts.s_bal.at[cand].set(s_bal_kj[ar, cnt_i]))
    new_carry = carry._replace(used=used, nonzero_used=nonzero, npods=npods,
                               cache=new_cache)
    return new_carry, assignments, mono_ok & norm_ok, depth_ok


@functools.partial(jax.jit, static_argnames=("cfg", "L", "K", "J"))
def _run_uniform_jit(cfg: ScoreConfig, na: NodeArrays, carry: Carry, x: PodXs,
                     table: PodTableDev, n_actual, L: int, K: int, J: int,
                     overlay=None):
    new_carry, assignments, ok, depth_ok = _uniform_core(
        cfg, na, carry, x, table, n_actual, L, K, J, overlay)
    # pack [assignments; exact; depth] into ONE i32[L+2]: a run costs the
    # caller exactly one device→host readback — and with chained runs,
    # none until the end of the drain (readback latency on a local chip:
    # not measured). packed[L] = semantic preconditions held (scan
    # otherwise); packed[L+1] = depth sufficed (escalate J otherwise).
    packed = jnp.concatenate([
        assignments,
        jnp.stack([ok, depth_ok]).astype(jnp.int32)])
    return new_carry, packed


@device_entry(cost=(32.0, 3.0))
def run_uniform(cfg: ScoreConfig, na: NodeArrays, carry: Carry, x: PodXs,
                table: PodTableDev, n_actual, L: int, K: int, J: int,
                overlay=None):
    """Ledger-instrumented entry for `_run_uniform_jit` (the closed-form
    top-L path; see its docstring for the exactness argument). Never
    donates: the scheduler keeps the input carry for rewind/replay."""
    return _run_uniform_jit, (cfg, na, carry, x, table, n_actual, L, K, J,
                              overlay)


# ---------------------------------------------------------------------------
# speculative wave placement: conflict-checked parallel group scheduling
# (arXiv:2508.04953 Tesserae-style batch placement with conflict repair,
# constrained to EXACT serial-greedy parity)


class WaveXs(NamedTuple):
    """Per-pod wave inputs ([W] = wave length, serial priority order)."""

    valid: jnp.ndarray   # bool [W]
    widx: jnp.ndarray    # i32 [W] — slot into the wave row set [S]


class _WaveState(NamedTuple):
    """In-dispatch scan state: node bookkeeping + the wave rows' group
    counters ([S] = distinct signatures in the wave) + conflict stats."""

    used: jnp.ndarray          # i64 [N, R]
    nonzero_used: jnp.ndarray  # i64 [N, 2]
    npods: jnp.ndarray         # i32 [N]
    fit_ok: jnp.ndarray        # bool [S, N]
    s_fit: jnp.ndarray         # i64 [S, N]
    s_bal: jnp.ndarray         # i64 [S, N]
    f_cnt: jnp.ndarray         # i32 [S, SC, N]
    s_cnt: jnp.ndarray         # i32 [S, SC, N]
    veto: jnp.ndarray          # i32 [S, N]
    a_cnt: jnp.ndarray         # i32 [S, TA, N]
    a_total: jnp.ndarray       # i64 [S]
    aa_cnt: jnp.ndarray        # i32 [S, TAA, N]
    iscore: jnp.ndarray        # i64 [S, N]
    cnt_sn: jnp.ndarray        # i32 [S, N] — accepted placements (fold input)
    clean: jnp.ndarray         # bool — no conflict seen yet
    n_conf: jnp.ndarray        # i32 — conflicting pods so far
    prefix: jnp.ndarray        # i32 — conflict-free prefix length
    # host-port bookkeeping (None unless the plan program compiles the
    # has_ports variant — a drain mixing host-port rows into the wave)
    ports: jnp.ndarray = None  # i32 [N, P]


def _sum_terms(x):
    """`x` i64 [S, T, N] summed over its term axis T as T - 1 elementwise
    adds: a TPU lowers a 64-bit sum over that middle axis to a transposing
    copy and an unfused reduce (about 0.5 ms a step at 8192 nodes)."""
    return functools.reduce(jnp.add, [x[:, t] for t in range(x.shape[1])])


def _run_plan_impl(cfg: ScoreConfig, na: NodeArrays, carry: Carry,
                        xs: WaveXs, table: PodTableDev, wt, gd: GroupsDev,
                        statics, fam: GroupFamilies, norm_live: bool,
                        has_groups: bool, has_ports: bool = False):
    """One wave of group-constrained pods in ONE device dispatch.

    Phase A (speculative parallel scoring): every distinct signature's full
    kernel set — static filters, taint/affinity/image scores, fit scores —
    is evaluated ONCE against the same pre-wave carry ([S, N] surfaces),
    and each signature's speculative argmax is recorded. This is where the
    wave wins: the expensive kernels run S times per wave instead of once
    per pod.

    Phase B (conflict detection + repair, serial priority order): a scan
    over the wave re-derives each pod's EXACT serial decision from the
    Phase-A surfaces plus the accumulated in-wave deltas — fit/score
    refreshed at the touched nodes, group counters carried for the wave's
    consumer rows, normalizations re-reduced per step. A pod whose exact
    argmax differs from its signature's speculative choice is a CONFLICT
    (capacity oversubscription, topology-skew movement, affinity surface
    change); it is repaired in place by taking the exact choice, so the
    wave's assignments are bit-identical to the serial scan in every case
    — an all-conflict wave degenerates to a serial re-evaluation without
    error, it just stops being fast. The conflict count and the
    conflict-free prefix length are returned for observability.

    Epilogue: the accepted placements fold into the FULL group carry with
    one batched pass (ops/groups.py wave_fold — additivity makes the fold
    order-independent), so the next wave (or scan segment) continues from
    an exact resident carry with no host round trip.

    Preconditions (the scheduler gates): single device, no nominated-pod
    overlay, every wave pod sig != 0 (no host ports), groups active, and
    `norm_live=False` only under ops.hostgreedy.static_norm_ok. Returns
    (new carry, packed i32 [W+2]): assignments, then n_conflicts, then the
    conflict-free prefix length."""
    from .groups import (GroupView, group_mask_view, group_scores_view,
                         wave_fold)

    gc = carry.groups
    S = wt.shape[0]
    n = na.cap.shape[0]
    fields = {name: getattr(table, name)[wt] for name in PodTableDev._fields}
    rows = PodRow(valid=jnp.ones((S,), bool),
                  sig=jnp.ones((S,), jnp.int32), **fields)

    # ---- Phase A: per-signature surfaces at the pre-wave carry. The
    # carry-independent ones arrive precomputed (wave_statics, cached by
    # the scheduler per signature); only the fit kernels evaluate here.
    static_mask, taint_raw, na_raw, s_img = statics

    def fit_one(pod: PodRow):
        fit_ok = fit_mask(na.cap, carry.used, carry.npods, na.allowed_pods,
                          pod.req)
        s_fit, s_bal = _fit_scores(cfg, na, carry, pod)
        return fit_ok, s_fit, s_bal

    fit0, sfit0, sbal0 = jax.vmap(fit_one)(rows)

    # wave-local group statics (gathered once; [S, ...]); a LEAN wave
    # (non-interacting signatures, no group constraints anywhere) carries
    # no group state at all — the issue's "disjoint signatures placed in
    # a single wave" case, which previously thrashed the one-slot
    # signature cache with a full kernel recompute on every alternation
    if has_groups:
        f_act = gd.spr_f_active[wt]
        f_skew = gd.spr_f_max_skew[wt]
        f_self = gd.spr_f_self[wt]
        f_minz = gc.spr_f_min_zero[wt]
        f_tv = gd.spr_f_tv[wt]
        f_elig = gd.spr_f_elig[wt]
        s_act = gd.spr_s_active[wt]
        s_skew = gd.spr_s_max_skew[wt]
        s_ishost = gd.spr_s_is_host[wt]
        s_tv = gd.spr_s_tv[wt]
        s_elig = gd.spr_s_elig[wt]
        s_keys = gd.spr_s_keys_ok[wt]
        s_dom = gd.spr_s_dom[wt]
        ra_act = gd.ipa_ra_active[wt]
        ra_tv = gd.ipa_ra_tv[wt]
        raa_act = gd.ipa_raa_active[wt]
        raa_tv = gd.ipa_raa_tv[wt]
        self_all = gd.ipa_self_all[wt]
        stc_tv = gd.ipa_stc_tv[wt]
        stp_tv = gd.ipa_stp_tv[wt]
        # pairwise [placed s → consumer s'] slices
        m_f = gd.m_spr_f[wt][:, wt]
        m_s = gd.m_spr_s[wt][:, wt]
        m_a = gd.m_ipa_a[wt][:, wt]
        m_aa = gd.m_ipa_aa[wt][:, wt]
        m_ex = gd.m_ipa_exist[wt][:, wt]
        w_c = gd.w_stc[wt][:, wt]
        w_p = gd.w_stp[wt][:, wt]

    st0 = _WaveState(
        used=carry.used, nonzero_used=carry.nonzero_used, npods=carry.npods,
        fit_ok=fit0, s_fit=sfit0, s_bal=sbal0,
        f_cnt=gc.spr_f_cnt[wt] if has_groups else None,
        s_cnt=gc.spr_s_cnt[wt] if has_groups else None,
        veto=gc.ipa_veto[wt] if has_groups else None,
        a_cnt=gc.ipa_a_cnt[wt] if has_groups else None,
        a_total=gc.ipa_a_total[wt] if has_groups else None,
        aa_cnt=gc.ipa_aa_cnt[wt] if has_groups else None,
        iscore=gc.ipa_score[wt] if has_groups else None,
        cnt_sn=jnp.zeros((S, n), jnp.int32) if has_groups else None,
        clean=jnp.bool_(True), n_conf=jnp.int32(0), prefix=jnp.int32(0),
        ports=carry.ports if has_ports else None)

    def _eval(stx: _WaveState, w):
        """Feasibility + total score of signature slot `w` at the state —
        the same formula code as the scan's _eval_pod, over the wave's
        maintained counters (GroupView shared with ops/groups.py)."""
        feasible = static_mask[w] & stx.fit_ok[w]
        if has_ports:
            # host-port rows evaluate the live ports carry every step —
            # exactly the scan's slow path for sig-0 pods (port-free rows
            # carry all-zero port_ids, so this is vacuously true for them)
            feasible &= ports_mask(stx.ports, rows.port_ids[w])
        if has_groups:
            view = GroupView(
                f_act=f_act[w], f_skew=f_skew[w], f_self=f_self[w],
                f_minz=f_minz[w], f_tv=f_tv[w], f_elig=f_elig[w],
                f_cnt=stx.f_cnt[w],
                s_act=s_act[w], s_skew=s_skew[w], s_is_host=s_ishost[w],
                s_tv=s_tv[w], s_keys_ok=s_keys[w], s_dom=s_dom[w],
                s_cnt=stx.s_cnt[w],
                ra_act=ra_act[w], ra_tv=ra_tv[w], raa_act=raa_act[w],
                raa_tv=raa_tv[w], self_all=self_all[w],
                veto=stx.veto[w], a_cnt=stx.a_cnt[w], a_total=stx.a_total[w],
                aa_cnt=stx.aa_cnt[w], iscore=stx.iscore[w])
            feasible &= group_mask_view(view, fam)
        if norm_live:
            s_taint = default_normalize(taint_raw[w], feasible, reverse=True)
            s_na = default_normalize(na_raw[w], feasible, reverse=False)
            tn = cfg.w_taint * s_taint + cfg.w_node_affinity * s_na
        else:
            # static_norm_ok precondition: every taint_raw/na_raw is zero,
            # so DefaultNormalize degenerates to the constants 100 / 0
            tn = cfg.w_taint * MAX_SCORE
        total = (cfg.w_fit * stx.s_fit[w] + cfg.w_balanced * stx.s_bal[w]
                 + tn + cfg.w_image * s_img[w])
        if has_groups:
            total = total + group_scores_view(cfg.w_spread, cfg.w_ipa, view,
                                              feasible, fam)
        return feasible, total

    # speculative choice per signature (the parallel argmax of Phase A)
    def spec_one(s):
        feas, tot = _eval(st0, s)
        masked = jnp.where(feas, tot, -1)
        b = jnp.argmax(masked).astype(jnp.int32)
        return jnp.where(masked[b] >= 0, b, jnp.int32(-1))

    spec_y = jax.vmap(spec_one)(jnp.arange(S, dtype=jnp.int32))

    cols = jnp.array(cfg.score_cols, jnp.int32)
    nzm = jnp.array(cfg.col_nonzero)
    slots = jnp.array(cfg.nonzero_slot, jnp.int32)

    def step(stx: _WaveState, x: WaveXs):
        w = x.widx
        feasible, total = _eval(stx, w)
        masked = jnp.where(feasible, total, -1)
        best = jnp.argmax(masked).astype(jnp.int32)
        assigned = (masked[best] >= 0) & x.valid
        g_i = assigned.astype(jnp.int32)
        req_w = rows.req[w]
        used = stx.used.at[best].add(jnp.where(assigned, req_w, 0))
        nzu = stx.nonzero_used.at[best].add(
            jnp.where(assigned, rows.nonzero_req[w], 0))
        npods = stx.npods.at[best].add(g_i.astype(stx.npods.dtype))

        # refresh the fit kernels of EVERY wave signature at the one
        # touched node (_row_refresh semantics, vmapped over rows)
        cap_row = na.cap[best]
        used_row = used[best]
        nz_row = nzu[best]
        npods_b = npods[best]
        allowed_b = na.allowed_pods[best]

        def refresh_one(row_s: PodRow):
            fit_b = ((npods_b + 1 <= allowed_b)
                     & jnp.all((row_s.req == 0)
                               | (used_row + row_s.req <= cap_row)))
            cap_r = cap_row[cols][None, :]
            used_nz_r = nz_row[slots] + row_s.nonzero_req[slots]
            used_pl_r = used_row[cols] + row_s.req[cols]
            used_cols_r = jnp.where(nzm, used_nz_r, used_pl_r)[None, :]
            s_fit_b = least_allocated(cfg, cap_r, used_cols_r)[0]
            s_bal_b = jnp.where(row_s.skip_balanced, 0,
                                balanced_allocation(cap_r,
                                                    used_pl_r[None, :])[0])
            return fit_b, s_fit_b, s_bal_b

        fit_b, sfit_b, sbal_b = jax.vmap(refresh_one)(rows)

        def put_col(arr, new):
            return arr.at[:, best].set(jnp.where(assigned, new,
                                                 arr[:, best]))

        fit_ok = put_col(stx.fit_ok, fit_b)
        s_fit = put_col(stx.s_fit, sfit_b)
        s_bal = put_col(stx.s_bal, sbal_b)

        # group counter updates for the wave's consumer rows — the
        # group_update increments with consumer axis U → S, placed row w
        f_cnt, s_cnt = stx.f_cnt, stx.s_cnt
        veto, a_cnt, a_total = stx.veto, stx.a_cnt, stx.a_total
        aa_cnt, iscore = stx.aa_cnt, stx.iscore
        if has_groups and fam.spr_f:
            tvb_f = f_tv[:, :, best]                  # [S, SC]
            eligb_f = f_elig[:, :, best]
            inc_f = ((m_f[w] & eligb_f)[:, :, None]
                     & (f_tv == tvb_f[:, :, None])
                     & (tvb_f[:, :, None] != 0))
            f_cnt = stx.f_cnt + g_i * inc_f.astype(jnp.int32)
        if has_groups and fam.spr_s:
            tvb_s = s_tv[:, :, best]
            eligb_s = s_elig[:, :, best]
            is_b = (jnp.arange(n, dtype=jnp.int32) == best)[None, None, :]
            share_s = jnp.where(s_ishost[:, :, None], is_b,
                                (s_tv == tvb_s[:, :, None])
                                & (tvb_s[:, :, None] != 0))
            gate_c = jnp.where(s_ishost, m_s[w], m_s[w] & eligb_s)
            s_cnt = stx.s_cnt + g_i * (
                gate_c[:, :, None] & share_s).astype(jnp.int32)
        if has_groups and fam.ipa_anti:
            tvb_p_anti = raa_tv[w, :, best]           # [TAA]
            share_anti = ((raa_tv[w] == tvb_p_anti[:, None])
                          & (tvb_p_anti[:, None] != 0))
            delta_veto = jnp.sum(m_ex[w][:, :, None] & share_anti[None],
                                 axis=1).astype(jnp.int32)
            veto = stx.veto + g_i * delta_veto
            tvb_aa = raa_tv[:, :, best]
            share_aa = ((raa_tv == tvb_aa[:, :, None])
                        & (tvb_aa[:, :, None] != 0))
            inc_aa = m_aa[w][:, :, None] & share_aa
            aa_cnt = stx.aa_cnt + g_i * inc_aa.astype(jnp.int32)
        if has_groups and fam.ipa_req:
            tvb_a = ra_tv[:, :, best]
            share_a = ((ra_tv == tvb_a[:, :, None])
                       & (tvb_a[:, :, None] != 0))
            inc_a = ((m_a[w][:, None] & ra_act)[:, :, None] & share_a)
            a_cnt = stx.a_cnt + g_i * inc_a.astype(jnp.int32)
            a_total = stx.a_total + (
                g_i * m_a[w]
                * jnp.sum(ra_act & (tvb_a != 0), axis=1)).astype(jnp.int64)
        if has_groups and fam.ipa_score:
            tvb_c = stc_tv[:, :, best]
            share_c = ((stc_tv == tvb_c[:, :, None])
                       & (tvb_c[:, :, None] != 0))
            d_cons = _sum_terms(w_c[w][:, :, None] * share_c)
            tvb_p = stp_tv[w, :, best]
            share_p = ((stp_tv[w] == tvb_p[:, None])
                       & (tvb_p[:, None] != 0))
            d_plcd = _sum_terms(w_p[w][:, :, None] * share_p[None])
            iscore = stx.iscore + assigned.astype(jnp.int64) * (
                d_cons + d_plcd)

        cnt_sn = (stx.cnt_sn.at[w, best].add(g_i) if has_groups else None)
        ports2 = stx.ports
        if has_ports:
            # place the pod's port ids into the first free slots of the
            # chosen node's row (_apply_assignment's exact port logic)
            prow = stx.ports[best]
            free = prow == 0
            rank = jnp.cumsum(free) - 1
            pp = rows.port_ids[w]
            nport = pp.shape[0]
            incoming = jnp.where((rank >= 0) & (rank < nport) & free,
                                 pp[jnp.clip(rank, 0, nport - 1)], 0)
            new_prow = jnp.where(free, incoming, prow)
            ports2 = stx.ports.at[best].set(
                jnp.where(assigned & jnp.any(pp != 0), new_prow, prow))
        y = jnp.where(assigned, best, jnp.int32(-1))
        conflict = x.valid & (y != spec_y[w])
        prefix = stx.prefix + (stx.clean & x.valid
                               & ~conflict).astype(jnp.int32)
        return _WaveState(
            used=used, nonzero_used=nzu, npods=npods,
            fit_ok=fit_ok, s_fit=s_fit, s_bal=s_bal,
            f_cnt=f_cnt, s_cnt=s_cnt, veto=veto, a_cnt=a_cnt,
            a_total=a_total, aa_cnt=aa_cnt, iscore=iscore,
            cnt_sn=cnt_sn, clean=stx.clean & ~conflict,
            n_conf=stx.n_conf + conflict.astype(jnp.int32),
            prefix=prefix, ports=ports2), y

    stf, ys = lax.scan(step, st0, xs)

    # fold the accepted placements into the FULL group carry (batched,
    # order-independent adds — ops/groups.py wave_fold)
    new_gc = (wave_fold(gd, gc, wt, stf.cnt_sn, fam=fam) if has_groups
              else carry.groups)
    new_carry = Carry(used=stf.used, nonzero_used=stf.nonzero_used,
                      npods=stf.npods,
                      ports=stf.ports if has_ports else carry.ports,
                      cache=carry.cache._replace(sig=jnp.int32(0)),
                      groups=new_gc)
    packed = jnp.concatenate(
        [ys, jnp.stack([stf.n_conf, stf.prefix])]).astype(jnp.int32)
    return new_carry, packed


@functools.lru_cache(maxsize=None)
def _run_plan_fn(donate: bool):
    return jax.jit(_run_plan_impl,
                   static_argnames=("cfg", "fam", "norm_live", "has_groups",
                                    "has_ports"),
                   donate_argnums=(2,) if donate else ())


@device_entry(donates=2, cost=(48.0, 3.0))
def run_plan(cfg: ScoreConfig, na: NodeArrays, carry: Carry, xs: WaveXs,
             table: PodTableDev, wt, gd: GroupsDev | None, statics,
             fam: GroupFamilies, norm_live: bool, has_groups: bool = True,
             has_ports: bool = False):
    """The drain compiler's program entry (kubernetes_tpu/compiler/): ONE
    compiled dispatch for an arbitrary mixed-signature span — group rows,
    group-free rows and (with `has_ports`) host-port rows alike, at any
    pow2 signature-lattice width S. Shares the wave-scan implementation
    (per-signature surfaces hoisted via `statics`, exact serial-order
    replay over the maintained counters), compiled with `has_ports` to
    additionally maintain the ports carry so sig-0 rows no longer force
    a span split. The input carry is DONATED on accelerator backends
    (run_batch's contract); CPU compiles without donation."""
    return _run_plan_fn, (cfg, na, carry, xs, table, wt, gd, statics, fam,
                          norm_live, has_groups, has_ports)


@functools.partial(jax.jit, static_argnames=("feats",))
def _wave_statics_jit(na: NodeArrays, table: PodTableDev, wt,
                      feats: tuple = (True, True, True)):
    """Carry-independent per-signature surfaces for the wave kernels —
    static filter mask (name/unschedulable/taints/selector; ports vacuous
    for sig != 0 rows), TaintToleration / preferred-affinity raw counts,
    ImageLocality score. `wt` i32 [S] table rows → [S, N] arrays. The
    scheduler caches the result per (table row, staging generation), so
    the expensive broadcast kernels run once per signature per node-state
    change instead of once per dispatch.

    `feats` = (taints, selectors, images): static host-derived flags; a
    False statically skips the matching kernel family — an unconstrained
    signature (no cluster taints, no selectors, no images) pays none of
    the padded broadcast compute."""
    has_taints, has_sel, has_img = feats
    fields = {name: getattr(table, name)[wt] for name in PodTableDev._fields}
    rows = PodRow(valid=jnp.ones(wt.shape, bool),
                  sig=jnp.ones(wt.shape, jnp.int32), **fields)
    n = na.valid.shape[0]

    def one(row: PodRow):
        m = na.valid
        m &= (row.node_name_id == 0) | (na.name_id == row.node_name_id)
        m &= ~na.unschedulable | row.tolerates_unsched
        if has_taints:
            m &= taint_filter_mask(na, row)
            traw = taint_prefer_count(na, row)
        else:
            traw = jnp.zeros((n,), jnp.int64)
        if has_sel:
            m &= selector_mask(na, row)
            naraw = preferred_affinity_score(na, row)
        else:
            naraw = jnp.zeros((n,), jnp.int64)
        simg = (image_locality_score(na, row) if has_img
                else jnp.zeros((n,), jnp.int64))
        return m, traw, naraw, simg

    return jax.vmap(one)(rows)


@device_entry(cost=(8.0, 2.0))
def wave_statics(na: NodeArrays, table: PodTableDev, wt,
                 feats: tuple = (True, True, True)):
    """Ledger-instrumented entry for `_wave_statics_jit`."""
    return _wave_statics_jit, (na, table, wt, feats)


class _SameWaveState(NamedTuple):
    """run_wave (same-signature) loop state."""

    used: jnp.ndarray          # i64 [N, R]
    nonzero_used: jnp.ndarray  # i64 [N, 2]
    npods: jnp.ndarray         # i32 [N]
    f_cnt: jnp.ndarray         # i32 [SC, N] — own-row spread filter counts
    veto: jnp.ndarray          # i32 [N] — own-row existing-anti veto
    aa_cnt: jnp.ndarray        # i32 [TAA, N] — own-row incoming-anti counts
    cnt_n: jnp.ndarray         # i32 [N] — accepted placements per node
    out: jnp.ndarray           # i32 [B] — assignments (-1 = none)
    done: jnp.ndarray          # i32 — pods resolved so far
    prog: jnp.ndarray          # bool — last merge wave made progress
    ok: jnp.ndarray            # bool — merge preconditions still hold
    waves: jnp.ndarray         # i32 — merge waves executed
    confs: jnp.ndarray         # i32 — conflict (prefix-cut) events
    first_prefix: jnp.ndarray  # i32 — first wave's accepted prefix length


def _run_wave_same_impl(cfg: ScoreConfig, na: NodeArrays, carry: Carry,
                        valid, table: PodTableDev, wt, gd: GroupsDev,
                        statics, K: int, J: int, Lw: int,
                        fam: GroupFamilies, norm_live: bool,
                        anti_term: int, merge_on: bool):
    """Speculative wave placement for a SAME-SIGNATURE run of group pods,
    one device dispatch for the whole span.

    Merge tier (a device while_loop of closed-form waves): each wave
    speculates the run's next placements in parallel — the run_uniform
    top-L merge over the [K, J] post-placement score matrix, extended with
    the group structure: an `anti_term` (the row's self-matching required
    anti-affinity) turns the merge into champion-per-topology-domain
    selection (each placement vetoes its whole domain, so only a domain's
    best node can ever be chosen), and the spread skew check is replayed
    per speculated placement at DOMAIN level (cnt0 + rank-in-domain vs the
    pre-wave minimum). The longest conflict-free prefix — no skew-mask
    flip, no depth overflow, no domain re-entry — is accepted, its deltas
    fold into the loop state, and the conflicted remainder re-enters the
    next wave re-anchored on the updated counts. Exactness preconditions
    are checked on the live data per wave (score-matrix monotonicity,
    flat inter-pod-affinity score surface over the feasible set, no
    dynamically skew-masked node at wave start); any failure stops the
    merge tier with `ok=False`.

    Serial tier: whatever the merge did not resolve (conflict-heavy or
    precondition-failing remainders — the worst-case all-conflict wave)
    is finished by an in-dispatch serial scan with the exact per-pod
    rule, so the kernel ALWAYS returns the full span's assignments,
    bit-identical to the host oracle's serial order.

    `valid` is a prefix mask (bool [B], B static); `wt` the scalar table
    row. Host-side gates (the scheduler checks): single device, no
    nominations, sig != 0, no ScheduleAnyway constraints on the row, no
    self-matching required affinity, no self score terms, at most one
    self-matching anti term (`anti_term`, -1 = none; static).

    Returns (carry, packed i32 [B + 4]): assignments, then
    [merge_waves, conflict_events, first_wave_prefix, serial_steps]."""
    from .groups import (INT32_MAX, GroupView, _dom_share, group_mask_view,
                         group_scores_view, wave_fold)

    gc = carry.groups
    B = valid.shape[0]
    n = na.cap.shape[0]
    W = jnp.sum(valid).astype(jnp.int32)
    fields = {name: getattr(table, name)[wt] for name in PodTableDev._fields}
    row = PodRow(valid=jnp.bool_(True), sig=jnp.int32(1), **fields)

    # carry-independent surfaces, hoisted out of the dispatch entirely
    # (the scheduler computes them once per signature via wave_statics)
    m0, taint_raw, na_raw, s_img = statics

    # own-row group statics
    f_act = gd.spr_f_active[wt]
    f_skew = gd.spr_f_max_skew[wt]
    f_self = gd.spr_f_self[wt]
    f_minz = gc.spr_f_min_zero[wt]
    f_tv = gd.spr_f_tv[wt]
    f_elig = gd.spr_f_elig[wt]
    f_dom = gd.spr_f_dom[wt]
    s_act = gd.spr_s_active[wt]
    s_skew = gd.spr_s_max_skew[wt]
    s_ishost = gd.spr_s_is_host[wt]
    s_tv = gd.spr_s_tv[wt]
    s_keys = gd.spr_s_keys_ok[wt]
    s_dom = gd.spr_s_dom[wt]
    s_cnt0 = gc.spr_s_cnt[wt]          # static: no self ScheduleAnyway
    ra_act = gd.ipa_ra_active[wt]
    ra_tv = gd.ipa_ra_tv[wt]
    raa_act = gd.ipa_raa_active[wt]
    raa_tv = gd.ipa_raa_tv[wt]
    raa_dom = gd.ipa_raa_dom[wt]
    self_all = gd.ipa_self_all[wt]
    a_cnt0 = gc.ipa_a_cnt[wt]          # static: no self required affinity
    a_total0 = gc.ipa_a_total[wt]
    iscore0 = gc.ipa_score[wt]         # static: no self score terms
    mf_self = gd.m_spr_f[wt, wt]       # [SC]
    mex_self = gd.m_ipa_exist[wt, wt]  # [TAA]
    maa_self = gd.m_ipa_aa[wt, wt]
    if anti_term >= 0:
        anti_tv = raa_tv[anti_term]
        anti_dom = raa_dom[anti_term]

    def eval_row(used, nz, npods, f_cnt, veto, aa_cnt):
        fit_ok = fit_mask(na.cap, used, npods, na.allowed_pods, row.req)
        c2 = carry._replace(used=used, nonzero_used=nz)
        s_fit, s_bal = _fit_scores(cfg, na, c2, row)
        view = GroupView(
            f_act=f_act, f_skew=f_skew, f_self=f_self, f_minz=f_minz,
            f_tv=f_tv, f_elig=f_elig, f_cnt=f_cnt,
            s_act=s_act, s_skew=s_skew, s_is_host=s_ishost, s_tv=s_tv,
            s_keys_ok=s_keys, s_dom=s_dom, s_cnt=s_cnt0,
            ra_act=ra_act, ra_tv=ra_tv, raa_act=raa_act, raa_tv=raa_tv,
            self_all=self_all, veto=veto, a_cnt=a_cnt0, a_total=a_total0,
            aa_cnt=aa_cnt, iscore=iscore0)
        gmask = m0 & group_mask_view(view, fam)
        feasible = gmask & fit_ok
        if norm_live:
            s_taint = default_normalize(taint_raw, feasible, reverse=True)
            s_na = default_normalize(na_raw, feasible, reverse=False)
            tn = cfg.w_taint * s_taint + cfg.w_node_affinity * s_na
        else:
            tn = cfg.w_taint * MAX_SCORE
        total = (cfg.w_fit * s_fit + cfg.w_balanced * s_bal + tn
                 + cfg.w_image * s_img)
        total = total + group_scores_view(cfg.w_spread, cfg.w_ipa, view,
                                          feasible, fam)
        return gmask, feasible, total

    # ---- merge tier -------------------------------------------------------

    def merge_cond(st: _SameWaveState):
        return st.ok & st.prog & (st.done < W)

    def merge_body(st: _SameWaveState):
        gmask, feasible0, total0 = eval_row(st.used, st.nonzero_used,
                                            st.npods, st.f_cnt, st.veto,
                                            st.aa_cnt)
        masked0 = jnp.where(feasible0, total0, jnp.int64(-1))
        # inter-pod score surface must be FLAT over the feasible set: its
        # normalized contribution is then identically 0 and stays 0 as
        # feasibility shrinks (the surface itself is static in-run)
        big = jnp.iinfo(jnp.int64).max
        isc_min = jnp.min(jnp.where(feasible0, iscore0, big))
        isc_max = jnp.max(jnp.where(feasible0, iscore0, -big))
        flat = isc_max <= isc_min
        # spread skew check must not mask ANY keyed node at wave start:
        # counts only grow and the pre-wave minimum only rises, so a wave
        # whose replayed counts stay under the bound never flips a mask bit
        if fam.spr_f:
            minv = jnp.min(jnp.where(f_elig, st.f_cnt, INT32_MAX), axis=-1)
            minv = jnp.where(f_minz, 0, minv)
            ok_cn = (st.f_cnt + f_self[:, None] - minv[:, None]
                     <= f_skew[:, None])
            start_inert = jnp.all(~f_act[:, None] | (f_tv == 0) | ok_cn)
        else:
            minv = jnp.zeros(f_skew.shape, jnp.int32)
            start_inert = jnp.bool_(True)

        _, cand = lax.top_k(masked0.astype(jnp.int32), K)
        cand = cand.astype(jnp.int32)
        if anti_term >= 0:
            # champion per anti-topology domain: every placement vetoes
            # its whole domain, so only a domain's (score desc, idx asc)
            # best can ever be chosen; keyless nodes are unconstrained
            keyN = masked0 * n - jnp.arange(n, dtype=jnp.int64)
            seg = jnp.full((n,), jnp.iinfo(jnp.int64).min).at[anti_dom].max(
                jnp.where(anti_tv != 0, keyN, jnp.iinfo(jnp.int64).min))
            champ = (anti_tv == 0) | ((anti_tv != 0) & (keyN == seg[anti_dom]))
            champ_cand = champ[cand][:, None]
            jcap = 1
        else:
            champ_cand = jnp.ones((K, 1), bool)
            jcap = J
        fit_kj, s_fit_kj, s_bal_kj = _uniform_matrix(
            cfg, na, st.used, st.npods, st.used, st.nonzero_used,
            cand, row, J)
        static_add = (cfg.w_taint * MAX_SCORE + cfg.w_image * s_img)[cand]
        score_kj = (cfg.w_fit * s_fit_kj + cfg.w_balanced * s_bal_kj
                    + static_add[:, None])
        jmask = jnp.arange(J)[None, :] < jcap
        masked_kj = jnp.where(gmask[cand][:, None] & champ_cand & fit_kj
                              & jmask, score_kj, jnp.int64(-1))
        mono_ok = jnp.all(masked_kj[:, 1:] <= masked_kj[:, :-1])

        # key = (score desc, node idx asc, j asc) — run_uniform's merge
        score_max = MAX_SCORE * (cfg.w_fit + cfg.w_balanced + cfg.w_taint
                                 + cfg.w_node_affinity + cfg.w_image)
        M = n * J
        key_dt = jnp.int32 if (score_max + 2) * M < 2 ** 31 else jnp.int64
        ent_id = (cand[:, None].astype(key_dt) * J
                  + jnp.arange(J, dtype=key_dt)[None, :])
        flat_key = (masked_kj.astype(key_dt) * key_dt(M) - ent_id).reshape(K * J)
        top_vals, flat_i = lax.top_k(flat_key, Lw)
        krank = (flat_i // J).astype(jnp.int32)
        node_i = cand[krank]
        j_i = (flat_i % J).astype(jnp.int32)
        avail = W - st.done
        sel_ok = (top_vals > -key_dt(M)) & (jnp.arange(Lw) < avail)

        # conflict detection over the speculated sequence
        if fam.spr_f:
            # replay the skew bound at domain level: entry i's new count =
            # cnt0(dom) + rank-in-domain + 1, against the EXACT evolving
            # minimum — min rises to min0+m once every eligible domain's
            # count reaches min0+m, tracked level by level so a balanced
            # fill (counts rising in lockstep) accepts the whole wave
            gate = (mf_self[None, :] & f_elig[:, node_i].T
                    & sel_ok[:, None])                     # [Lw, SC]
            dom_ic = f_dom[:, node_i].T                    # [Lw, SC]
            eq = dom_ic[None, :, :] == dom_ic[:, None, :]  # [i, j, SC]
            lower = jnp.tril(jnp.ones((Lw, Lw), bool), -1)
            r_ic = jnp.sum(eq & gate[None, :, :] & lower[:, :, None],
                           axis=1).astype(jnp.int32)
            newcnt = st.f_cnt[:, node_i].T + r_ic + 1
            M_CAP = 32
            lvlv = minv[:, None] + jnp.arange(1, M_CAP + 1,
                                              dtype=jnp.int32)[None, :]
            # D_need[c, m]: eligible domains still below min0+m. A domain
            # id IS the index of one of its nodes, so the domain's count
            # can be read at that slot — one scatter marks the domains
            # with an eligible member, then the level compare is
            # elementwise over the marked slots only.
            elig_dom = jax.vmap(
                lambda dom_c, el_c: jnp.zeros((n,), jnp.int32).at[dom_c].max(
                    el_c.astype(jnp.int32)))(f_dom, f_elig)     # [SC, N]
            d_need = jnp.sum(
                (elig_dom[:, None, :] > 0)
                & (st.f_cnt[:, None, :] < lvlv[:, :, None]),
                axis=2).astype(jnp.int32)                        # [SC, M]
            comp = (gate[:, :, None]
                    & (newcnt[:, :, None] == lvlv[None, :, :]))  # [Lw,SC,M]
            cum_excl = jnp.cumsum(comp, axis=0) - comp
            reached = cum_excl >= d_need[None, :, :]
            lvl_up = jnp.sum(reached, axis=2).astype(jnp.int32)  # [Lw, SC]
            min_i = jnp.where(f_minz[None, :], 0, minv[None, :] + lvl_up)
            viol = jnp.any(f_act[None, :] & gate
                           & ((newcnt + f_self[None, :] - min_i
                               > f_skew[None, :])
                              | (lvl_up >= M_CAP)), axis=1)
        else:
            viol = jnp.zeros((Lw,), bool)
        if anti_term >= 0:
            # a keyless (no-topology) node hides its deeper entries from
            # the jcap=1 merge: cut after it so the next wave re-offers it
            viol |= anti_tv[node_i] == 0
        else:
            # depth cut: a candidate consuming its last matrix entry may
            # have deserved more — stop there, the next wave re-anchors
            viol |= j_i == J - 1
        viol &= sel_ok
        excl = jnp.cumsum(viol) - viol
        accept = sel_ok & (excl == 0)
        iter_ok = mono_ok & flat & start_inert
        accept &= iter_ok
        a = jnp.sum(accept).astype(jnp.int32)

        cnt_add = jnp.zeros((n,), jnp.int32).at[node_i].add(
            accept.astype(jnp.int32))
        used2 = st.used + cnt_add[:, None].astype(jnp.int64) * row.req[None, :]
        nz2 = (st.nonzero_used
               + cnt_add[:, None].astype(jnp.int64) * row.nonzero_req[None, :])
        npods2 = st.npods + cnt_add.astype(st.npods.dtype)
        f_cnt2 = st.f_cnt
        if fam.spr_f:
            inc = _dom_share(f_tv, f_dom,
                             f_elig.astype(jnp.int32) * cnt_add[None, :])
            f_cnt2 = st.f_cnt + jnp.where(mf_self[:, None], inc, 0)
        veto2, aa2 = st.veto, st.aa_cnt
        if fam.ipa_anti:
            sh = _dom_share(raa_tv, raa_dom,
                            jnp.broadcast_to(cnt_add[None, :], raa_tv.shape))
            veto2 = st.veto + jnp.sum(
                jnp.where(mex_self[:, None], sh, 0), axis=0).astype(jnp.int32)
            aa2 = st.aa_cnt + jnp.where(maa_self[:, None], sh,
                                        0).astype(jnp.int32)
        rank = jnp.cumsum(accept) - accept
        pos = jnp.where(accept, st.done + rank, B)
        out2 = st.out.at[pos].set(node_i, mode="drop")
        return _SameWaveState(
            used=used2, nonzero_used=nz2, npods=npods2, f_cnt=f_cnt2,
            veto=veto2, aa_cnt=aa2, cnt_n=st.cnt_n + cnt_add, out=out2,
            done=st.done + a, prog=a > 0, ok=st.ok & iter_ok,
            waves=st.waves + 1,
            confs=st.confs + ((a < avail) & iter_ok).astype(jnp.int32),
            first_prefix=jnp.where(st.waves == 0, a, st.first_prefix))

    st = _SameWaveState(
        used=carry.used, nonzero_used=carry.nonzero_used, npods=carry.npods,
        f_cnt=gc.spr_f_cnt[wt], veto=gc.ipa_veto[wt],
        aa_cnt=gc.ipa_aa_cnt[wt], cnt_n=jnp.zeros((n,), jnp.int32),
        out=jnp.full((B,), -1, jnp.int32), done=jnp.int32(0),
        prog=jnp.bool_(True), ok=jnp.bool_(True), waves=jnp.int32(0),
        confs=jnp.int32(0), first_prefix=jnp.int32(-1))
    if merge_on and not norm_live:
        st = lax.while_loop(merge_cond, merge_body, st)

    # ---- serial tier: finish the remainder with the exact per-pod rule
    def serial_cond(sv):
        st, steps = sv
        return st.done < W

    def serial_body(sv):
        st, steps = sv
        _, feasible, total = eval_row(st.used, st.nonzero_used, st.npods,
                                      st.f_cnt, st.veto, st.aa_cnt)
        masked = jnp.where(feasible, total, -1)
        best = jnp.argmax(masked).astype(jnp.int32)
        assigned = masked[best] >= 0
        g = assigned.astype(jnp.int32)
        used2 = st.used.at[best].add(jnp.where(assigned, row.req, 0))
        nz2 = st.nonzero_used.at[best].add(
            jnp.where(assigned, row.nonzero_req, 0))
        npods2 = st.npods.at[best].add(g.astype(st.npods.dtype))
        f_cnt2 = st.f_cnt
        if fam.spr_f:
            tvb = f_tv[:, best]
            inc = ((mf_self & f_elig[:, best])[:, None]
                   & (f_tv == tvb[:, None]) & (tvb[:, None] != 0))
            f_cnt2 = st.f_cnt + g * inc.astype(jnp.int32)
        veto2, aa2 = st.veto, st.aa_cnt
        if fam.ipa_anti:
            tvb_a = raa_tv[:, best]
            share = (raa_tv == tvb_a[:, None]) & (tvb_a[:, None] != 0)
            veto2 = st.veto + g * jnp.sum(
                mex_self[:, None] & share, axis=0).astype(jnp.int32)
            aa2 = st.aa_cnt + g * (maa_self[:, None] & share).astype(jnp.int32)
        out2 = st.out.at[st.done].set(jnp.where(assigned, best, -1))
        st2 = st._replace(used=used2, nonzero_used=nz2, npods=npods2,
                          f_cnt=f_cnt2, veto=veto2, aa_cnt=aa2,
                          cnt_n=st.cnt_n.at[best].add(g), out=out2,
                          done=st.done + 1)
        return st2, steps + 1

    st, serial_steps = lax.while_loop(serial_cond, serial_body,
                                      (st, jnp.int32(0)))

    new_gc = wave_fold(gd, gc, jnp.reshape(wt, (1,)), st.cnt_n[None, :],
                       fam=fam)
    new_carry = Carry(used=st.used, nonzero_used=st.nonzero_used,
                      npods=st.npods, ports=carry.ports,
                      cache=carry.cache._replace(sig=jnp.int32(0)),
                      groups=new_gc)
    packed = jnp.concatenate(
        [st.out, jnp.stack([st.waves, st.confs, st.first_prefix,
                            serial_steps])]).astype(jnp.int32)
    return new_carry, packed


@functools.lru_cache(maxsize=None)
def _run_wave_same_fn(donate: bool):
    return jax.jit(_run_wave_same_impl,
                   static_argnames=("cfg", "K", "J", "Lw", "fam",
                                    "norm_live", "anti_term", "merge_on"),
                   donate_argnums=(2,) if donate else ())


@device_entry(donates=2, cost=(64.0, 4.0))
def run_wave(cfg: ScoreConfig, na: NodeArrays, carry: Carry, valid,
             table: PodTableDev, wt, gd: GroupsDev, statics, K: int, J: int,
             fam: GroupFamilies, norm_live: bool, anti_term: int = -1,
             merge_on: bool = True, Lw: int = 512):
    """Jitted entry for the same-signature wave kernel; the input carry is
    donated on accelerator backends (see run_batch). `statics` is the
    signature's wave_statics row ([N] each); `Lw` caps the speculated
    entries per merge wave (span-length independent, so one executable
    serves every drain size)."""
    Lw = min(Lw, valid.shape[0])
    return _run_wave_same_fn, (cfg, na, carry, valid, table, wt, gd,
                               statics, K, J, Lw, fam, norm_live, anti_term,
                               merge_on)


# ---------------------------------------------------------------------------
# preemption dry-run kernel family (preemption.go:775 DryRunPreemption,
# SURVEY §7 step 8): the per-candidate-node host loop becomes one gathered
# program over the candidate axis


def pod_row_from_table(table, u: int, sig: int = 0) -> PodRow:
    """One signature row of a (numpy) PodTable as the kernels' PodRow."""
    import numpy as np
    fields = {name: getattr(table, name)[u] for name in PodTableDev._fields}
    return PodRow(valid=np.bool_(True), sig=np.int32(sig), **fields)


def _dry_run_spread_ok(sp: DryRunSpread, removed):
    """Spread feasibility for the preemptor on every candidate, given
    `removed` i32 [C, SC] matching victims currently removed. Mirrors the
    host filter (podtopologyspread.py filter): missing key → infeasible;
    matchNum + selfMatch − min > maxSkew → infeasible, with the
    criticalPaths closed form min(x, other) (groups.spread_dry_run_tensors)
    and the minDomains zero-floor."""
    x = sp.cnt0 - removed
    min_eff = jnp.where(sp.min_zero[None, :], 0,
                        jnp.minimum(x, sp.other_min))
    ok = x + sp.self_match[None, :] - min_eff <= sp.max_skew[None, :]
    return jnp.all(sp.tv_ok & ok, axis=1)


@jax.jit
def _dry_run_select_victims_jit(na: NodeArrays, pod: PodRow, cand,
                                victim_req, victim_valid, ovl_used,
                                ovl_npods,
                                spread: DryRunSpread | None = None):
    """Batched select_victims_on_node (default_preemption.go:583) over the
    candidate-node axis.

    cand         i32 [C]      node-row indices into `na` (padding repeats a
                              real row; the caller ignores padded outputs)
    victim_req   i64 [C,V,R]  potential victims' request vectors, REPRIEVE
                              order (PDB-violating first, then by priority
                              desc / creation asc — built host-side)
    victim_valid bool [C,V]
    ovl_used     i64 [C,R]    nominated-pod resources (the two-pass
    ovl_npods    i32 [C]      RunFilterPluginsWithNominatedPods overlay:
                              only ≥-priority nominations, self excluded)
    spread       victim count tensors when the preemptor carries
                 DoNotSchedule spread constraints (groups.DryRunSpread)

    Returns bool [C, V+1]: column 0 = the preemptor fits with every victim
    removed (candidate viable); column 1+v = victim v was reprieved (added
    back most-important-first while the preemptor still fits). The caller
    must only pass preemptors without host ports (the ports carry is not
    simulated), without pod (anti-)affinity, and on clusters without
    existing required-anti-affinity pods — everything else is exact.

    Monotonicity argument for the overlay: the host runs the filter twice
    (with and without nominated pods); resources and spread counts are
    additive, so with-nominated feasibility implies without-nominated —
    one overlaid pass is exact for the eligible subset."""
    na_c = NodeArrays(*(x[cand] for x in na))
    m = na_c.valid
    m &= (pod.node_name_id == 0) | (na_c.name_id == pod.node_name_id)
    m &= ~na_c.unschedulable | pod.tolerates_unsched
    m &= taint_filter_mask(na_c, pod)
    m &= selector_mask(na_c, pod)
    nv = jnp.sum(victim_valid, axis=1).astype(na_c.npods.dtype)
    total_req = jnp.sum(jnp.where(victim_valid[:, :, None], victim_req, 0),
                        axis=1)
    base_used = na_c.used + ovl_used - total_req
    base_npods = na_c.npods + ovl_npods - nv
    fits = m & fit_mask(na_c.cap, base_used, base_npods, na_c.allowed_pods,
                        pod.req)
    if spread is not None:
        vm = spread.vic_match.astype(jnp.int32)          # [C, V, SC]
        rm0 = jnp.sum(jnp.where(victim_valid[:, :, None], vm, 0), axis=1)
        fits &= _dry_run_spread_ok(spread, rm0)
        xs = (jnp.swapaxes(victim_req, 0, 1), victim_valid.T,
              jnp.swapaxes(vm, 0, 1))
        removed0 = rm0
    else:
        xs = (jnp.swapaxes(victim_req, 0, 1), victim_valid.T,
              jnp.zeros((victim_valid.shape[1], victim_valid.shape[0], 0),
                        jnp.int32))
        removed0 = jnp.zeros((victim_valid.shape[0], 0), jnp.int32)

    def step(carry, x):
        used, npods, removed = carry
        req_v, valid_v, match_v = x
        t_used = used + req_v
        t_npods = npods + 1
        ok = valid_v & (t_npods + 1 <= na_c.allowed_pods)
        ok &= jnp.all((pod.req[None, :] == 0)
                      | (t_used + pod.req[None, :] <= na_c.cap), axis=1)
        t_removed = removed - match_v
        if spread is not None:
            ok &= _dry_run_spread_ok(spread, t_removed)
        used = jnp.where(ok[:, None], t_used, used)
        npods = jnp.where(ok, t_npods, npods)
        removed = jnp.where(ok[:, None], t_removed, removed)
        return (used, npods, removed), ok

    carry0 = (base_used, base_npods, removed0)
    _, reprieved = lax.scan(step, carry0, xs)
    return jnp.concatenate([fits[:, None], reprieved.T], axis=1)


@device_entry("dry_run", cost=(40.0, 3.0))
def dry_run_select_victims(na: NodeArrays, pod: PodRow, cand,
                           victim_req, victim_valid, ovl_used, ovl_npods,
                           spread: DryRunSpread | None = None):
    """Ledger-instrumented entry for `_dry_run_select_victims_jit`."""
    return _dry_run_select_victims_jit, (na, pod, cand, victim_req,
                                         victim_valid, ovl_used, ovl_npods,
                                         spread)


class Retire(NamedTuple):
    """Bound pods leaving the resident carry, grouped by signature row
    ([S] = distinct rows, padded by repeating the first with zero
    counts)."""

    wt: object           # i32 [S] — table rows
    cnt: object          # i32 [S, N] — pods of row s deleted from node n
    req: object          # i64 [S, R] — the rows' request vectors
    nonzero_req: object  # i64 [S, 2]
    port_ids: object     # i32 [S, P] — interned host-port ids (0 = none)


@functools.partial(jax.jit, static_argnames=("fam",))
def _retire_pods_jit(carry: Carry, rt: Retire, gd: GroupsDev | None,
                     fam: GroupFamilies | None):
    cnt64 = rt.cnt.astype(jnp.int64)
    # a TPU has no 64-bit integer dot: multiply and sum over the rows
    used = carry.used - jnp.sum(cnt64[:, :, None] * rt.req[:, None, :],
                                axis=0)
    nonzero = carry.nonzero_used - jnp.sum(
        cnt64[:, :, None] * rt.nonzero_req[:, None, :], axis=0)
    npods = carry.npods - jnp.sum(rt.cnt, axis=0).astype(carry.npods.dtype)
    # a host port id sits in at most one slot of a node's row (ports_mask
    # refuses a collision), so clearing every slot a retired row's ids
    # hold frees exactly the retired pods' ports
    held = jnp.any((rt.cnt[:, :, None, None] > 0)
                   & (carry.ports[None, :, :, None]
                      == rt.port_ids[:, None, None, :])
                   & (rt.port_ids[:, None, None, :] != 0), axis=(0, 3))
    groups = carry.groups
    if groups is not None:
        groups = wave_fold(gd, groups, rt.wt, -rt.cnt, fam)
    return carry._replace(
        used=used, nonzero_used=nonzero, npods=npods,
        ports=jnp.where(held, 0, carry.ports),
        cache=carry.cache._replace(sig=jnp.int32(0)), groups=groups)


@device_entry(cost=(8.0, 2.0))
def retire_pods(carry: Carry, rt: Retire, gd: GroupsDev | None = None,
                fam: GroupFamilies | None = None):
    """Fold deleted bound pods out of the resident carry: the negative of
    their placements. The node usage loses each row's requests, pod count
    and host ports at its nodes; the group counts take `wave_fold` with
    the counts negated (every count is a gated sum over placed pods,
    linear in the placement counts, on static node labels), so the carry
    equals a reseed from the snapshot without the pods. The signature
    cache is invalidated: freed capacity moves its fit and scores.
    Non-donating: the input carry may still be read by an in-flight
    drain's probe."""
    return _retire_pods_jit, (carry, rt, gd, fam)


def initial_carry(na: NodeArrays, groups: GroupCarry | None = None) -> Carry:
    n = na.npods.shape[0]
    zero_cache = SigCache(
        sig=jnp.int32(0),
        static_mask=jnp.zeros((n,), bool),
        taint_raw=jnp.zeros((n,), jnp.int64),
        na_raw=jnp.zeros((n,), jnp.int64),
        s_img=jnp.zeros((n,), jnp.int64),
        fit_ok=jnp.zeros((n,), bool),
        s_fit=jnp.zeros((n,), jnp.int64),
        s_bal=jnp.zeros((n,), jnp.int64),
    )
    # COPY the seeded node state: the carry's buffers are donated to the
    # device programs (run_batch/run_wave consume their input carry), so
    # they must never alias the resident NodeArrays
    return Carry(used=jnp.array(na.used), nonzero_used=jnp.array(na.nonzero_used),
                 npods=jnp.array(na.npods), ports=jnp.array(na.ports),
                 cache=zero_cache, groups=groups)
