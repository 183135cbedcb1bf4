"""Group kernels: PodTopologySpread + InterPodAffinity on device.

The reference evaluates these plugins per pod with topologyPair→count hash
maps rebuilt every cycle (podtopologyspread/filtering.go:237-312,
interpodaffinity/filtering.go:204-273). The TPU form replaces each map with a
per-NODE count vector shared across nodes with equal topology value: for a
map keyed (topologyKey, value), `cnt[n] = map[(key, tv(n))]` — the device
never materializes the map, only its gather along the node axis. Counts ride
the scan carry and are updated after every placement with one vectorized
"same-topology-value" broadcast, which reproduces the reference's
AddPod/RemovePod incremental semantics (filtering.go:157-178, :322-341)
without any host round-trip.

Three layers:

- `GroupsDev` — static per-(signature, node) tensors: interned topology
  values per constraint/term, count-eligibility masks (node inclusion
  policies, common.go:43-57), and the pairwise signature match matrices that
  say whether a pod of signature u contributes to the counts of signature v.
  Recomputed host-side when the node set or the signature table changes.
- `GroupCarry` — the dynamic counts (spread match counts per DoNotSchedule /
  ScheduleAnyway constraint, the three inter-pod affinity maps of
  filtering.go:45-57, and the symmetric preferred-affinity score surface of
  scoring.go:81-124). Seeded host-side from the live snapshot by REUSING the
  host plugins' PreFilter/PreScore (guaranteeing seed parity), then carried
  forward on device.
- eval/update kernels called from the scan step in ops/program.py.

`GroupManager` (host) owns signature-row parsing, the match matrices, and
seeding. Pods whose constraints exceed the padded dims fall back to the host
oracle individually — never the whole batch.
"""

from __future__ import annotations

from contextlib import nullcontext
from dataclasses import dataclass
from typing import NamedTuple, Optional

import numpy as np

INT32_MAX = np.int32(2**31 - 1)

MAX_NODE_SCORE = 100

LABEL_HOSTNAME = "kubernetes.io/hostname"


# ---------------------------------------------------------------------------
# dims


@dataclass
class GroupDims:
    spread_constraints: int = 2   # SC — per action (DoNotSchedule / ScheduleAnyway)
    ipa_req_terms: int = 2        # TA — required affinity terms
    ipa_anti_terms: int = 2       # TAA — required anti-affinity terms
    ipa_cons_terms: int = 4       # CT — consumer-side preferred (score) terms
    ipa_plcd_terms: int = 6       # PT — placed-side score terms (req_a + preferred)


# ---------------------------------------------------------------------------
# device structures


class GroupsDev(NamedTuple):
    """Static per-table tensors ([U] = signature rows, [N] = node axis)."""

    # spread DoNotSchedule constraints (filtering.go)
    spr_f_active: object      # bool [U, SC]
    spr_f_max_skew: object    # i32 [U, SC]
    spr_f_self: object        # i32 [U, SC] — selfMatchNum (filtering.go:338)
    spr_f_tv: object          # i32 [U, SC, N] — node's interned topo value (0 = absent)
    spr_f_elig: object        # bool [U, SC, N] — counted node (keys + inclusion)
    spr_f_dom: object         # i32 [U, SC, N] — dense domain id (wave fold)
    # spread ScheduleAnyway constraints (scoring.go)
    spr_s_active: object      # bool [U, SC]
    spr_s_max_skew: object    # i32 [U, SC]
    spr_s_is_host: object     # bool [U, SC] — hostname key: per-node counts
    spr_s_tv: object          # i32 [U, SC, N]
    spr_s_elig: object        # bool [U, SC, N]
    spr_s_keys_ok: object     # bool [U, N] — all score topo keys present
    spr_s_dom: object         # i32 [U, SC, N] — dense domain id (first node idx w/ tv)
    # inter-pod affinity required terms (filtering.go)
    ipa_ra_active: object     # bool [U, TA]
    ipa_ra_tv: object         # i32 [U, TA, N]
    ipa_ra_dom: object        # i32 [U, TA, N] — dense domain id (wave fold)
    ipa_raa_active: object    # bool [U, TAA]
    ipa_raa_tv: object        # i32 [U, TAA, N]
    ipa_raa_dom: object       # i32 [U, TAA, N]
    ipa_self_all: object      # bool [U] — pod matches all own affinity terms
    # inter-pod affinity score terms (scoring.go)
    ipa_stc_tv: object        # i32 [U, CT, N] — consumer (incoming) pref terms
    ipa_stc_dom: object       # i32 [U, CT, N]
    ipa_stp_tv: object        # i32 [U, PT, N] — placed (existing) side terms
    ipa_stp_dom: object       # i32 [U, PT, N]
    # pairwise signature match matrices [placed-row, consumer-row, ...]
    m_spr_f: object           # bool [U, U, SC]
    m_spr_s: object           # bool [U, U, SC]
    m_ipa_a: object           # bool [U, U] — placed matches ALL consumer req terms
    m_ipa_aa: object          # bool [U, U, TAA] — per consumer anti term
    m_ipa_exist: object       # bool [U, U, TAA] — placed's anti term matches consumer
    w_stc: object             # i64 [U, U, CT] — signed weight (0 = no match)
    w_stp: object             # i64 [U, U, PT]


class GroupCarry(NamedTuple):
    """Dynamic counts riding the scan carry."""

    spr_f_cnt: object         # i32 [U, SC, N]
    spr_f_min_zero: object    # bool [U, SC] — eligible domains < minDomains
    spr_s_cnt: object         # i32 [U, SC, N]
    ipa_veto: object          # i32 [U, N] — existingAntiAffinityCounts per node
    ipa_a_cnt: object         # i32 [U, TA, N]
    ipa_a_total: object       # i64 [U] — affinityCounts map emptiness tracker
    ipa_aa_cnt: object        # i32 [U, TAA, N]
    ipa_score: object         # i64 [U, N] — symmetric topology score surface


class GroupFamilies(NamedTuple):
    """Static (jit-key) activation mask per constraint family.

    When a family is provably inactive — no signature row carries it and its
    seeded counts are zero — every one of its carry updates is identically
    zero and every one of its mask/score contributions is the identity, so
    the kernels skip it AT TRACE TIME. This matters enormously on TPU: a
    spread-only workload compiles a program with no inter-pod-affinity
    compute at all (≈5-8× per scan step), which is what XLA's constant
    folder would do if the tensors were compile-time constants — but keyed
    on a 5-bool mask instead of the tensor VALUES, so the executable is
    reused across group-state rebuilds.

    Pass-through of an inactive family's counts stays exact across later
    activation: a newly added signature row re-seeds its own counts from the
    live snapshot (scatter_new_rows), and existing rows' counts could only
    have received zero increments while the family was inactive."""

    spr_f: bool = True
    spr_s: bool = True
    ipa_req: bool = True
    ipa_anti: bool = True
    ipa_score: bool = True


ALL_FAMILIES = GroupFamilies()


# ---------------------------------------------------------------------------
# preemption dry-run: victim count tensors (spread deltas)


class DryRunSpread(NamedTuple):
    """PodTopologySpread victim-delta tensors for the batched preemption
    dry-run (ops/program.py dry_run_select_victims). [C] = candidate nodes,
    [V] = padded victim slots, [SC] = the preemptor's DoNotSchedule
    constraints. Built host-side by `spread_dry_run_tensors` from the SAME
    plugin PreFilter state the host oracle seeds, so the device check is by
    construction over the oracle's quantities."""

    max_skew: object      # i32 [SC]
    self_match: object    # i32 [SC] — selfMatchNum (filtering.go:338)
    min_zero: object      # bool [SC] — eligible domains < minDomains
    tv_ok: object         # bool [C, SC] — candidate has the topology key
    cnt0: object          # i32 [C, SC] — seeded match count in the
    #                       candidate's own topology domain
    other_min: object     # i32 [C, SC] — criticalPaths companion minimum
    #                       (see spread_dry_run_tensors)
    vic_match: object     # bool [C, V, SC] — victim moves constraint count


def spread_dry_run_tensors(s, pod, cand_infos, victims, c_pad: int,
                           v_pad: int) -> DryRunSpread:
    """Victim count tensors for the spread deltas of one preemption dry run.

    `s` is the preemptor's seeded podtopologyspread _PreFilterState (the
    host plugin's own PreFilter over ALL nodes), `cand_infos` the candidate
    NodeInfos and `victims[c]` each candidate's potential victims in
    reprieve order.

    criticalPaths closed form: a dry run only ever updates ONE topology
    value per candidate (all victims live on that node), so the evolving
    two-entry min tracker (filtering.go:97-136) reduces exactly to
    min(x, other) where x is the candidate domain's live count and `other`
    is n1 when the candidate's domain IS the tracked minimum (v0) and n0
    otherwise. This covers every update sequence including the
    untracked→tracked transition: once x dips below n1 the host tracker
    evicts its v1 and pairs (d, x) with (v0, n0), so later increases still
    compare against n0 — the same value the formula uses throughout."""
    from ..plugins.podtopologyspread import (_match_node_inclusion_policies,
                                             _node_has_all_topology_keys)

    cons = s.constraints
    SC = len(cons)
    max_skew = np.array([c.max_skew for c in cons], np.int32)
    self_match = np.array(
        [1 if c.selector.matches(pod.metadata.labels) else 0 for c in cons],
        np.int32)
    min_zero = np.array(
        [len(s.tp_value_to_match_num[j]) < c.min_domains
         for j, c in enumerate(cons)], bool)
    tv_ok = np.zeros((c_pad, SC), bool)
    cnt0 = np.zeros((c_pad, SC), np.int32)
    other_min = np.full((c_pad, SC), INT32_MAX, np.int32)
    vic_match = np.zeros((c_pad, v_pad, SC), bool)
    for ci, ni in enumerate(cand_infos):
        labels = ni.node.metadata.labels
        for j, c in enumerate(cons):
            tv = labels.get(c.topology_key)
            if tv is None:
                continue
            tv_ok[ci, j] = True
            cnt0[ci, j] = s.tp_value_to_match_num[j].get(tv, 0)
            cp = s.critical_paths[j]
            other_min[ci, j] = min(cp.n1 if tv == cp.v0 else cp.n0,
                                   int(INT32_MAX))
        # _update_with_pod gates EVERY constraint update on the node having
        # all topology keys (podtopologyspread.py:331) — mirror exactly
        if not _node_has_all_topology_keys(labels, cons):
            continue
        for j, c in enumerate(cons):
            if not _match_node_inclusion_policies(c, pod, ni):
                continue
            for vi, pi in enumerate(victims[ci]):
                vp = pi.pod
                if (vp.namespace == pod.namespace
                        and c.selector.matches(vp.metadata.labels)):
                    vic_match[ci, vi, j] = True
    return DryRunSpread(max_skew=max_skew, self_match=self_match,
                        min_zero=min_zero, tv_ok=tv_ok, cnt0=cnt0,
                        other_min=other_min, vic_match=vic_match)


# ---------------------------------------------------------------------------
# device kernels


class GroupView(NamedTuple):
    """One signature row's gathered group tensors — the shared input of
    `group_mask_view` / `group_scores_view`. Built by `view_of` (gather
    from GroupsDev/GroupCarry by tidx) on the scan path, and from the
    wave kernel's maintained in-scan counters (ops/program.py run_wave) —
    both paths evaluate the SAME formula code."""

    f_act: object       # bool [SC]
    f_skew: object      # i32 [SC]
    f_self: object      # i32 [SC]
    f_minz: object      # bool [SC]
    f_tv: object        # i32 [SC, N]
    f_elig: object      # bool [SC, N]
    f_cnt: object       # i32 [SC, N]
    s_act: object       # bool [SC]
    s_skew: object      # i32 [SC]
    s_is_host: object   # bool [SC]
    s_tv: object        # i32 [SC, N]
    s_keys_ok: object   # bool [N]
    s_dom: object       # i32 [SC, N]
    s_cnt: object       # i32 [SC, N]
    ra_act: object      # bool [TA]
    ra_tv: object       # i32 [TA, N]
    raa_act: object     # bool [TAA]
    raa_tv: object      # i32 [TAA, N]
    self_all: object    # bool
    veto: object        # i32 [N]
    a_cnt: object       # i32 [TA, N]
    a_total: object     # i64
    aa_cnt: object      # i32 [TAA, N]
    iscore: object      # i64 [N]


def view_of(gd: GroupsDev, gc: GroupCarry, tidx) -> GroupView:
    return GroupView(
        f_act=gd.spr_f_active[tidx], f_skew=gd.spr_f_max_skew[tidx],
        f_self=gd.spr_f_self[tidx], f_minz=gc.spr_f_min_zero[tidx],
        f_tv=gd.spr_f_tv[tidx], f_elig=gd.spr_f_elig[tidx],
        f_cnt=gc.spr_f_cnt[tidx],
        s_act=gd.spr_s_active[tidx], s_skew=gd.spr_s_max_skew[tidx],
        s_is_host=gd.spr_s_is_host[tidx], s_tv=gd.spr_s_tv[tidx],
        s_keys_ok=gd.spr_s_keys_ok[tidx], s_dom=gd.spr_s_dom[tidx],
        s_cnt=gc.spr_s_cnt[tidx],
        ra_act=gd.ipa_ra_active[tidx], ra_tv=gd.ipa_ra_tv[tidx],
        raa_act=gd.ipa_raa_active[tidx], raa_tv=gd.ipa_raa_tv[tidx],
        self_all=gd.ipa_self_all[tidx],
        veto=gc.ipa_veto[tidx], a_cnt=gc.ipa_a_cnt[tidx],
        a_total=gc.ipa_a_total[tidx], aa_cnt=gc.ipa_aa_cnt[tidx],
        iscore=gc.ipa_score[tidx])


def group_mask_view(v: GroupView, fam: GroupFamilies,
                    axis: Optional[str] = None):
    import jax.numpy as jnp

    from .collectives import pmin

    n = v.veto.shape[-1]
    mask = jnp.ones((n,), bool)

    if fam.spr_f:
        # -- spread skew (DoNotSchedule)
        minv = jnp.min(jnp.where(v.f_elig, v.f_cnt, INT32_MAX), axis=-1)
        if axis is not None:
            minv = pmin(minv, axis)
        # fewer eligible domains than minDomains (incl. zero domains) ⇒
        # min = 0 (filtering.go:66-77)
        minv = jnp.where(v.f_minz, 0, minv)
        ok = (v.f_cnt + v.f_self[:, None] - minv[:, None]
              <= v.f_skew[:, None])
        # node missing the topology key ⇒ UnschedulableAndUnresolvable
        mask &= jnp.all(~v.f_act[:, None] | ((v.f_tv != 0) & ok), axis=0)

    if fam.ipa_anti:
        # -- existing pods' required anti-affinity (filtering.go:204-228)
        mask &= v.veto == 0
        # -- incoming required anti-affinity
        mask &= ~jnp.any(v.raa_act[:, None] & (v.raa_tv != 0)
                         & (v.aa_cnt > 0), axis=0)

    if fam.ipa_req:
        # -- incoming required affinity (incl. the first-pod-in-series
        # escape hatch, filtering.go:381-397)
        tv_all = jnp.all(~v.ra_act[:, None] | (v.ra_tv != 0), axis=0)
        pods_exist = jnp.all(~v.ra_act[:, None] | (v.a_cnt > 0), axis=0)
        # sum==0 <=> len==0 for the reference's affinityCounts map: seed
        # entries are built by counting (strictly positive) and the device
        # path only ever increments — if a RemovePod-style decrement is
        # ever added, this test must switch to an explicit entry count
        escape = (v.a_total == 0) & v.self_all
        mask &= jnp.where(jnp.any(v.ra_act), tv_all & (pods_exist | escape),
                          True)

    return mask


def group_mask(gd: GroupsDev, gc: GroupCarry, tidx, axis: Optional[str] = None,
               fam: Optional[GroupFamilies] = None):
    """Feasibility over the node axis for the pod signature `tidx`:
    spread skew check (filtering.go:314-360) AND the three inter-pod
    affinity checks (filtering.go:405-432). `fam` statically skips families
    whose contribution is provably the identity (see GroupFamilies)."""
    return group_mask_view(view_of(gd, gc, tidx), fam or ALL_FAMILIES, axis)


def group_reason_masks(gd: GroupsDev, gc: GroupCarry, tidx,
                       fam: Optional[GroupFamilies] = None,
                       axis: Optional[str] = None):
    """Diagnosis companion to `group_mask`: the SAME formulas, split into
    the five per-node failure masks the host filters report —
    (spr_missing, spr_skew, aff_fail, anti_fail, exist_fail), each bool
    [N]. Spread attributes each node to its FIRST failing constraint (the
    host filter iterates constraints in order and returns on the first
    violation, podtopologyspread filtering); the caller layers these under
    the host's plugin order (spread before inter-pod affinity)."""
    import jax.numpy as jnp

    from .collectives import pmin

    fam = fam or ALL_FAMILIES
    v = view_of(gd, gc, tidx)
    n = v.veto.shape[-1]
    false = jnp.zeros((n,), bool)
    spr_missing = spr_skew = aff_fail = anti_fail = exist_fail = false

    if fam.spr_f:
        minv = jnp.min(jnp.where(v.f_elig, v.f_cnt, INT32_MAX), axis=-1)
        if axis is not None:
            minv = pmin(minv, axis)
        minv = jnp.where(v.f_minz, 0, minv)
        ok = (v.f_cnt + v.f_self[:, None] - minv[:, None]
              <= v.f_skew[:, None])
        missing_c = v.f_act[:, None] & (v.f_tv == 0)        # [SC, N]
        fail_c = v.f_act[:, None] & ((v.f_tv == 0) | ~ok)
        any_fail = jnp.any(fail_c, axis=0)
        first_c = jnp.argmax(fail_c, axis=0)                # [N]
        first_missing = jnp.take_along_axis(
            missing_c, first_c[None, :], axis=0)[0]
        spr_missing = any_fail & first_missing
        spr_skew = any_fail & ~first_missing

    if fam.ipa_req:
        tv_all = jnp.all(~v.ra_act[:, None] | (v.ra_tv != 0), axis=0)
        pods_exist = jnp.all(~v.ra_act[:, None] | (v.a_cnt > 0), axis=0)
        escape = (v.a_total == 0) & v.self_all
        aff_fail = jnp.any(v.ra_act) & ~(tv_all & (pods_exist | escape))

    if fam.ipa_anti:
        anti_fail = jnp.any(v.raa_act[:, None] & (v.raa_tv != 0)
                            & (v.aa_cnt > 0), axis=0)
        exist_fail = v.veto != 0

    return spr_missing, spr_skew, aff_fail, anti_fail, exist_fail


def group_scores_view(w_spread: int, w_ipa: int, v: GroupView, feasible,
                      fam: GroupFamilies, axis: Optional[str] = None,
                      n_global: Optional[int] = None):
    import jax.numpy as jnp
    from jax import lax

    from .collectives import pmax, pmin

    N = feasible.shape[0]
    if n_global is None:
        n_global = N

    def _gmin(x):
        return pmin(x, axis) if axis is not None else x

    def _gmax(x):
        return pmax(x, axis) if axis is not None else x

    def _gsum(x):
        return lax.psum(x, axis) if axis is not None else x

    if not fam.spr_s and not fam.ipa_score:
        return jnp.zeros((N,), jnp.int64)
    if not fam.spr_s:
        return w_ipa * _ipa_norm_scores(v.iscore, feasible, _gmin, _gmax)
    # ---- PodTopologySpread (scoring.go:199-271) ----
    has_s = jnp.any(v.s_act)
    scored = feasible & v.s_keys_ok
    npart = _gsum(jnp.sum(scored))
    # per-constraint domain count among scored nodes (topologyNormalizingWeight)
    dom = v.s_dom                                   # [SC, N]
    flags = jnp.zeros((dom.shape[0], n_global), jnp.int32)
    flags = flags.at[jnp.arange(dom.shape[0])[:, None], dom].max(
        jnp.broadcast_to(scored.astype(jnp.int32), dom.shape))
    if axis is not None:
        flags = lax.psum(flags, axis)
    distinct = jnp.sum(flags > 0, axis=1)           # [SC]
    size = jnp.where(v.s_is_host, npart, distinct)
    weight = jnp.log(size.astype(jnp.float64) + 2.0)  # [SC]
    contrib = jnp.where(
        v.s_act[:, None] & (v.s_tv != 0),
        v.s_cnt.astype(jnp.float64) * weight[:, None]
        + (v.s_skew[:, None] - 1).astype(jnp.float64),
        0.0)
    raw = jnp.round(jnp.sum(contrib, axis=0)).astype(jnp.int64)  # [N]
    # normalize (host plugin normalize_scores: MAX·(max+min−s)//max)
    minv = _gmin(jnp.min(jnp.where(scored, raw, INT32_MAX)))
    maxv = _gmax(jnp.max(jnp.where(scored, raw, 0)))
    norm = jnp.where(maxv == 0, MAX_NODE_SCORE,
                     MAX_NODE_SCORE * (maxv + minv - raw) // jnp.maximum(maxv, 1))
    spread_score = jnp.where(has_s & scored, norm, 0)
    # ignored (missing-keys) nodes score 0; infeasible rows are masked later

    if not fam.ipa_score:
        return w_spread * spread_score
    return (w_spread * spread_score
            + w_ipa * _ipa_norm_scores(v.iscore, feasible, _gmin, _gmax))


def group_scores(w_spread: int, w_ipa: int, gd: GroupsDev, gc: GroupCarry,
                 tidx, feasible, axis: Optional[str] = None,
                 n_global: Optional[int] = None,
                 fam: Optional[GroupFamilies] = None):
    """Weighted PodTopologySpread + InterPodAffinity score over the node
    axis, already normalized per the host plugins' Normalize formulas.
    `feasible` is the FULL filtered set (all plugins), matching the host
    runtime's normalize-over-filtered-list semantics. `n_global` is the
    unsharded node-axis length (defaults to the local length)."""
    return group_scores_view(w_spread, w_ipa, view_of(gd, gc, tidx),
                             feasible, fam or ALL_FAMILIES, axis, n_global)


def _ipa_norm_scores(s, feasible, _gmin, _gmax):
    """InterPodAffinity normalized score surface (scoring.go:263-293).
    `s`: the gathered i64 [N] symmetric topology score surface."""
    import jax.numpy as jnp

    big = jnp.iinfo(jnp.int64).max
    minv2 = _gmin(jnp.min(jnp.where(feasible, s, big)))
    maxv2 = _gmax(jnp.max(jnp.where(feasible, s, -big)))
    diff = maxv2 - minv2
    return jnp.where(
        diff > 0,
        (MAX_NODE_SCORE * (s - minv2).astype(jnp.float64)
         / jnp.maximum(diff, 1).astype(jnp.float64)),
        0.0).astype(jnp.int64)


def group_update(gd: GroupsDev, gc: GroupCarry, tidx, pick, is_chosen, gate,
                 fam: Optional[GroupFamilies] = None):
    """Carry update after placing a pod of signature `tidx`.

    `pick(arr)` extracts `arr[..., b]` for the chosen node b (the sharded
    path substitutes a cross-shard broadcast); `is_chosen` is bool[N_local]
    marking the chosen node's row (all-false on non-owning shards); `gate` is
    the placement-happened scalar. Mirrors a fresh recount after the
    placement: counts are additive over pods and node labels are static, so
    the incremental broadcast equals the reference's per-cycle rebuild."""
    import jax.numpy as jnp

    fam = fam or ALL_FAMILIES
    u = tidx
    gate_i = gate.astype(jnp.int32)
    spr_f_cnt, spr_s_cnt = gc.spr_f_cnt, gc.spr_s_cnt
    ipa_veto, ipa_a_cnt = gc.ipa_veto, gc.ipa_a_cnt
    ipa_a_total, ipa_aa_cnt = gc.ipa_a_total, gc.ipa_aa_cnt
    ipa_score = gc.ipa_score

    if fam.spr_f:
        # spread filter counts: +1 at every node sharing the chosen node's
        # topology value, per consumer constraint the placed pod matches,
        # iff the chosen node is count-eligible for that constraint
        tvb_f = pick(gd.spr_f_tv)                   # [U, SC]
        eligb_f = pick(gd.spr_f_elig)               # [U, SC]
        inc_f = ((gd.m_spr_f[u] & eligb_f)[:, :, None]
                 & (gd.spr_f_tv == tvb_f[:, :, None])
                 & (tvb_f[:, :, None] != 0))
        spr_f_cnt = gc.spr_f_cnt + gate_i * inc_f.astype(jnp.int32)

    if fam.spr_s:
        # spread score counts: hostname constraints count the node's own
        # pods (scoring.go score()); other keys share by topology value
        tvb_s = pick(gd.spr_s_tv)
        eligb_s = pick(gd.spr_s_elig)
        is_b = is_chosen[None, None, :]             # [1, 1, N]
        share_s = jnp.where(gd.spr_s_is_host[:, :, None], is_b,
                            (gd.spr_s_tv == tvb_s[:, :, None])
                            & (tvb_s[:, :, None] != 0))
        gate_c = jnp.where(gd.spr_s_is_host, gd.m_spr_s[u],
                           gd.m_spr_s[u] & eligb_s)
        spr_s_cnt = gc.spr_s_cnt + gate_i * (
            gate_c[:, :, None] & share_s).astype(jnp.int32)

    if fam.ipa_anti:
        # existing-anti veto: the placed pod's own required anti terms add
        # a (term.key, tv(b)) pair for every consumer signature they match
        tvb_p_anti = pick(gd.ipa_raa_tv)[u]         # [TAA]
        share_anti = ((gd.ipa_raa_tv[u] == tvb_p_anti[:, None])
                      & (tvb_p_anti[:, None] != 0))  # [TAA, N]
        delta_veto = jnp.sum(
            gd.m_ipa_exist[u][:, :, None] & share_anti[None],
            axis=1).astype(jnp.int32)               # [U, N]
        ipa_veto = gc.ipa_veto + gate_i * delta_veto
        # incoming-anti counts (per consumer term)
        tvb_aa = pick(gd.ipa_raa_tv)                # [U, TAA]
        share_aa = ((gd.ipa_raa_tv == tvb_aa[:, :, None])
                    & (tvb_aa[:, :, None] != 0))
        inc_aa = gd.m_ipa_aa[u][:, :, None] & share_aa
        ipa_aa_cnt = gc.ipa_aa_cnt + gate_i * inc_aa.astype(jnp.int32)

    if fam.ipa_req:
        # incoming-affinity counts: placed pod matching ALL of a consumer's
        # required terms bumps each term's (key, tv(b)) pair
        tvb_a = pick(gd.ipa_ra_tv)                  # [U, TA]
        share_a = ((gd.ipa_ra_tv == tvb_a[:, :, None])
                   & (tvb_a[:, :, None] != 0))
        inc_a = ((gd.m_ipa_a[u][:, None] & gd.ipa_ra_active)[:, :, None]
                 & share_a)
        ipa_a_cnt = gc.ipa_a_cnt + gate_i * inc_a.astype(jnp.int32)
        ipa_a_total = gc.ipa_a_total + (
            gate_i * gd.m_ipa_a[u]
            * jnp.sum(gd.ipa_ra_active & (tvb_a != 0), axis=1)
        ).astype(jnp.int64)

    if fam.ipa_score:
        # symmetric score surface: consumer-side preferred terms matching
        # the placed pod, plus placed-side (req×hardWeight + preferred)
        # terms matching the consumer (scoring.go:81-124)
        tvb_c = pick(gd.ipa_stc_tv)                 # [U, CT]
        share_c = ((gd.ipa_stc_tv == tvb_c[:, :, None])
                   & (tvb_c[:, :, None] != 0))
        d_cons = jnp.sum(gd.w_stc[u][:, :, None] * share_c, axis=1)  # [U, N]
        tvb_p = pick(gd.ipa_stp_tv)[u]              # [PT]
        share_p = (gd.ipa_stp_tv[u] == tvb_p[:, None]) & (tvb_p[:, None] != 0)
        d_plcd = jnp.sum(gd.w_stp[u][:, :, None] * share_p[None], axis=1)
        ipa_score = gc.ipa_score + gate.astype(jnp.int64) * (d_cons + d_plcd)

    return GroupCarry(spr_f_cnt=spr_f_cnt, spr_f_min_zero=gc.spr_f_min_zero,
                      spr_s_cnt=spr_s_cnt, ipa_veto=ipa_veto,
                      ipa_a_cnt=ipa_a_cnt, ipa_a_total=ipa_a_total,
                      ipa_aa_cnt=ipa_aa_cnt, ipa_score=ipa_score)


# ---------------------------------------------------------------------------
# host side: row parsing, match matrices, node data, seeding


@dataclass
class GroupRowInfo:
    """Host-parsed group constraints for one signature row."""

    pod: object                    # representative pod (signature-identical)
    f_constraints: list            # spread _Constraint, DoNotSchedule
    s_constraints: list            # spread _Constraint, ScheduleAnyway
    req_a: list                    # merged-ns ParsedTerm (incoming affinity)
    req_aa: list                   # merged-ns ParsedTerm (incoming anti)
    req_aa_raw: list               # raw ParsedTerm (existing-pod side)
    stc_terms: list                # [(ParsedTerm, ±weight)] consumer score terms
    stp_terms: list                # [(ParsedTerm, ±weight)] placed score terms
    self_all: bool

    @property
    def has_groups(self) -> bool:
        return bool(self.f_constraints or self.s_constraints or self.req_a
                    or self.req_aa or self.stc_terms or self.stp_terms)


class GroupManager:
    """Owns per-signature-row group data + pairwise match matrices.

    Parsing and matching REUSE the host plugins' code paths
    (podtopologyspread._parse_constraints / _count_pods_match_selector,
    interpodaffinity.parse_pod_affinity_terms / ParsedTerm.matches), so the
    device program's inputs are by construction the same quantities the host
    oracle computes."""

    def __init__(self, state, spread_plugin=None, ipa_plugin=None,
                 dims: Optional[GroupDims] = None, table_rows: int = 16):
        from ..plugins.interpodaffinity import InterPodAffinity
        from ..plugins.podtopologyspread import PodTopologySpread

        from ..ingest.groupcols import NodeLabelColumns
        self.state = state
        self.pts = spread_plugin or PodTopologySpread()
        self.ipa = ipa_plugin or InterPodAffinity()
        self.dims = dims or GroupDims()
        self.rows: list[Optional[GroupRowInfo]] = []
        self._alloc(table_rows)
        self.group_row_count = 0   # rows with any group constraints
        # per-statics-generation columnar label views shared by node_data
        # and seed_counts (ingest/groupcols.py): the per-call O(N) tv /
        # dom / presence walks now run once per node-state change
        self.cols = NodeLabelColumns(state)
        # entered around each row's inter-pod-affinity half of
        # seed_counts: the owner's timing phase, a no-op by default
        self.ipa_phase = nullcontext

    # -- storage --------------------------------------------------------------

    def _alloc(self, U: int) -> None:
        d = self.dims
        self.U = U
        self.spr_f_active = np.zeros((U, d.spread_constraints), bool)
        self.spr_f_max_skew = np.zeros((U, d.spread_constraints), np.int32)
        self.spr_f_self = np.zeros((U, d.spread_constraints), np.int32)
        self.spr_s_active = np.zeros((U, d.spread_constraints), bool)
        self.spr_s_max_skew = np.zeros((U, d.spread_constraints), np.int32)
        self.spr_s_is_host = np.zeros((U, d.spread_constraints), bool)
        self.ipa_ra_active = np.zeros((U, d.ipa_req_terms), bool)
        self.ipa_raa_active = np.zeros((U, d.ipa_anti_terms), bool)
        self.ipa_self_all = np.zeros((U,), bool)
        self.m_spr_f = np.zeros((U, U, d.spread_constraints), bool)
        self.m_spr_s = np.zeros((U, U, d.spread_constraints), bool)
        self.m_ipa_a = np.zeros((U, U), bool)
        self.m_ipa_aa = np.zeros((U, U, d.ipa_anti_terms), bool)
        self.m_ipa_exist = np.zeros((U, U, d.ipa_anti_terms), bool)
        self.w_stc = np.zeros((U, U, d.ipa_cons_terms), np.int64)
        self.w_stp = np.zeros((U, U, d.ipa_plcd_terms), np.int64)
        # interaction graph: interacts[p, c] — placing a pod of row p can
        # move row c's group counts/scores (the build-time signature the
        # wave scheduler consults; state/batch.py BatchBuilder.wave_info)
        self.interacts = np.zeros((U, U), bool)

    # pairwise [U, U, ...] matrices vs per-row [U, ...] arrays: classified
    # by NAME, never by shape — a table_rows value that coincides with a
    # term dimension must not flip a per-row array into the pairwise path
    # (cf. sharding.py's _GD_NODE_FIELDS approach)
    _PAIRWISE_FIELDS = frozenset(
        {"m_spr_f", "m_spr_s", "m_ipa_a", "m_ipa_aa", "m_ipa_exist",
         "w_stc", "w_stp"})
    _ROW_FIELDS = ("spr_f_active", "spr_f_max_skew", "spr_f_self",
                   "spr_s_active", "spr_s_max_skew", "spr_s_is_host",
                   "ipa_ra_active", "ipa_raa_active", "ipa_self_all")

    def grow(self, U: int) -> None:
        names = (self._ROW_FIELDS + tuple(self._PAIRWISE_FIELDS)
                 + ("interacts",))
        old = {name: getattr(self, name) for name in names}
        u0 = len(self.rows)
        self._alloc(U)
        for name, arr in old.items():
            new = getattr(self, name)
            if name in self._PAIRWISE_FIELDS or name == "interacts":
                new[:u0, :u0] = arr[:u0, :u0]
            else:
                new[:u0] = arr[:u0]

    def reset(self) -> None:
        self.rows.clear()
        self._alloc(self.U)
        self.group_row_count = 0

    # -- row addition ---------------------------------------------------------

    def add_row(self, u: int, pod) -> None:
        """Parse + store row u; raises BatchCapacityError when the pod's
        constraints exceed the padded dims (that pod goes to the host
        oracle individually)."""
        from ..api.types import UnsatisfiableConstraintAction as UCA
        from ..plugins.interpodaffinity import (
            WeightedTerm, _pod_matches_all_affinity_terms,
            parse_pod_affinity_terms)
        from ..state.batch import BatchCapacityError

        d = self.dims
        f_cons = self.pts._get_constraints(pod, UCA.DO_NOT_SCHEDULE.value)
        s_cons = self.pts._get_constraints(pod, UCA.SCHEDULE_ANYWAY.value)
        if (self.pts.system_defaulted
                and not pod.spec.topology_spread_constraints
                and (f_cons or s_cons)):
            # relaxed require_all semantics of system defaulting have no
            # tensor form (scoring.go requireAllTopologies=false)
            raise BatchCapacityError("system-defaulted spread: host path")
        if len(f_cons) > d.spread_constraints or len(s_cons) > d.spread_constraints:
            raise BatchCapacityError("too many spread constraints")

        req_a, req_aa_raw, pref_a, pref_aa = parse_pod_affinity_terms(pod)
        if self.ipa.args.ignore_preferred_terms_of_existing_pods and (
                req_a or req_aa_raw or pref_a or pref_aa):
            raise BatchCapacityError("ignorePreferredTermsOfExistingPods: host path")
        req_a_m = [self.ipa._merge_term_namespaces(t) for t in req_a]
        req_aa_m = [self.ipa._merge_term_namespaces(t) for t in req_aa_raw]
        if len(req_a_m) > d.ipa_req_terms or len(req_aa_m) > d.ipa_anti_terms:
            raise BatchCapacityError("too many inter-pod affinity terms")
        # consumer-side score terms: incoming pod's MERGED preferred terms
        stc = ([(WeightedTerm(self.ipa._merge_term_namespaces(w.term), w.weight).term,
                 w.weight) for w in pref_a]
               + [(self.ipa._merge_term_namespaces(w.term), -w.weight)
                  for w in pref_aa])
        # placed-side score terms: RAW required (× hard weight) + preferred
        hw = self.ipa.args.hard_pod_affinity_weight
        stp = ([(t, hw) for t in req_a] if hw > 0 else [])
        stp += [(w.term, w.weight) for w in pref_a]
        stp += [(w.term, -w.weight) for w in pref_aa]
        if len(stc) > d.ipa_cons_terms or len(stp) > d.ipa_plcd_terms:
            raise BatchCapacityError("too many preferred affinity terms")

        info = GroupRowInfo(
            pod=pod, f_constraints=f_cons, s_constraints=s_cons,
            req_a=req_a_m, req_aa=req_aa_m, req_aa_raw=req_aa_raw,
            stc_terms=stc, stp_terms=stp,
            self_all=_pod_matches_all_affinity_terms(req_a_m, pod))
        while len(self.rows) <= u:
            self.rows.append(None)
        self.rows[u] = info
        if info.has_groups:
            self.group_row_count += 1

        # per-row scalars
        for j, c in enumerate(f_cons):
            self.spr_f_active[u, j] = True
            self.spr_f_max_skew[u, j] = c.max_skew
            self.spr_f_self[u, j] = 1 if c.selector.matches(pod.metadata.labels) else 0
        for j, c in enumerate(s_cons):
            self.spr_s_active[u, j] = True
            self.spr_s_max_skew[u, j] = c.max_skew
            self.spr_s_is_host[u, j] = c.topology_key == LABEL_HOSTNAME
        for t in range(len(req_a_m)):
            self.ipa_ra_active[u, t] = True
        for t in range(len(req_aa_m)):
            self.ipa_raa_active[u, t] = True
        self.ipa_self_all[u] = info.self_all

        # pairwise match matrices vs every existing row (both directions)
        for v, other in enumerate(self.rows):
            if other is None:
                continue
            self._fill_pair(u, info, v, other)
            if v != u:
                self._fill_pair(v, other, u, info)

    def _fill_pair(self, pu: int, placed: GroupRowInfo,
                   cu: int, cons: GroupRowInfo) -> None:
        """[placed → consumer] match entries."""
        from ..plugins.interpodaffinity import _pod_matches_all_affinity_terms
        from ..plugins.podtopologyspread import (_count_pods_match_selector,
                                                 _selector_empty)

        ppod, cpod = placed.pod, cons.pod
        same_ns = ppod.namespace == cpod.namespace
        for j, c in enumerate(cons.f_constraints):
            self.m_spr_f[pu, cu, j] = (same_ns and not _selector_empty(c.selector)
                                       and c.selector.matches(ppod.metadata.labels))
        for j, c in enumerate(cons.s_constraints):
            self.m_spr_s[pu, cu, j] = (same_ns and not _selector_empty(c.selector)
                                       and c.selector.matches(ppod.metadata.labels))
        self.m_ipa_a[pu, cu] = _pod_matches_all_affinity_terms(cons.req_a, ppod)
        for t, term in enumerate(cons.req_aa):
            self.m_ipa_aa[pu, cu, t] = term.matches(ppod, None)
        ns_labels = self.ipa.ns_lister.labels_of(cpod.namespace)
        for t, term in enumerate(placed.req_aa_raw):
            self.m_ipa_exist[pu, cu, t] = term.matches(cpod, ns_labels)
        for t, (term, w) in enumerate(cons.stc_terms):
            self.w_stc[pu, cu, t] = w if term.matches(ppod, None) else 0
        for t, (term, w) in enumerate(placed.stp_terms):
            self.w_stp[pu, cu, t] = w if term.matches(cpod, ns_labels) else 0
        self.interacts[pu, cu] = bool(
            self.m_spr_f[pu, cu].any() or self.m_spr_s[pu, cu].any()
            or self.m_ipa_a[pu, cu] or self.m_ipa_aa[pu, cu].any()
            or self.m_ipa_exist[pu, cu].any()
            or self.w_stc[pu, cu].any() or self.w_stp[pu, cu].any())

    def any_groups(self) -> bool:
        return self.group_row_count > 0

    # -- node-dependent statics ----------------------------------------------

    def _node_rows(self, snapshot) -> list:
        """[(row index, NodeInfo)] for the snapshot's nodes — built once
        per build/scatter and shared between node_data and seed_counts
        (the 2×O(N) name-lookup walks used to run per call)."""
        st = self.state
        N = st.dims.nodes
        nis = [(st.node_index.get(ni.name), ni)
               for ni in snapshot.node_info_list]
        return [(idx, ni) for idx, ni in nis if idx is not None and idx < N]

    def node_data(self, snapshot, rows: range, nis=None):
        """tv / eligibility / domain arrays for the given row slice against
        the CURRENT node set, laid out in ClusterState row order. Returns a
        dict of numpy arrays shaped like the matching GroupsDev fields but
        with a leading axis of len(rows)."""
        from ..plugins.node_basics import find_matching_untolerated_taint
        from ..plugins.nodeaffinity import required_node_affinity_matches
        from ..plugins.podtopologyspread import HONOR

        d = self.dims
        st = self.state
        N = st.dims.nodes
        SC, TA, TAA = d.spread_constraints, d.ipa_req_terms, d.ipa_anti_terms
        CT, PT = d.ipa_cons_terms, d.ipa_plcd_terms
        R = len(rows)
        out = dict(
            spr_f_tv=np.zeros((R, SC, N), np.int32),
            spr_f_elig=np.zeros((R, SC, N), bool),
            spr_f_dom=np.zeros((R, SC, N), np.int32),
            spr_s_tv=np.zeros((R, SC, N), np.int32),
            spr_s_elig=np.zeros((R, SC, N), bool),
            spr_s_keys_ok=np.zeros((R, N), bool),
            spr_s_dom=np.zeros((R, SC, N), np.int32),
            ipa_ra_tv=np.zeros((R, TA, N), np.int32),
            ipa_ra_dom=np.zeros((R, TA, N), np.int32),
            ipa_raa_tv=np.zeros((R, TAA, N), np.int32),
            ipa_raa_dom=np.zeros((R, TAA, N), np.int32),
            ipa_stc_tv=np.zeros((R, CT, N), np.int32),
            ipa_stc_dom=np.zeros((R, CT, N), np.int32),
            ipa_stp_tv=np.zeros((R, PT, N), np.int32),
            ipa_stp_dom=np.zeros((R, PT, N), np.int32),
        )
        if nis is None:
            nis = self._node_rows(snapshot)
        # persistent per-statics-generation columns (ingest/groupcols.py):
        # a topology key's interned tv vector is a property of the node
        # set, not of the row OR the call — the O(N) label walk now runs
        # once per node-state change instead of once per build_dev call
        # (the scheduler.py reseed/host-greedy/diagnosis sites all land
        # here), and once it did run, every row/constraint/term shares it.
        cols = self.cols.sync(nis)
        tv_vec = cols.tv
        dom_of_key = cols.dom

        def keys_ok_vec(keys: list[str]) -> np.ndarray:
            return cols.keys_ok(tuple(keys))

        def elig_vec(c, pod, keys: list[str]) -> np.ndarray:
            """Count-eligibility per node (common.go:43-57). The common
            case — no required node affinity on the pod, taints policy
            Ignore — is pure vector math; only HONOR policies walk nodes."""
            ok = keys_ok_vec(keys)
            trivial_affinity = (
                c.node_affinity_policy != HONOR
                or (not pod.spec.node_selector
                    and not (pod.spec.affinity
                             and pod.spec.affinity.node_affinity
                             and pod.spec.affinity.node_affinity.required)))
            if trivial_affinity and c.node_taints_policy != HONOR:
                return ok
            ok = ok.copy()   # keys_ok vectors are cached: never mutate
            for idx, ni in nis:
                if not ok[idx]:
                    continue
                labels = ni.node.metadata.labels
                good = True
                if c.node_affinity_policy == HONOR and not trivial_affinity:
                    good = required_node_affinity_matches(pod, labels,
                                                          ni.name)
                if good and c.node_taints_policy == HONOR:
                    good = find_matching_untolerated_taint(
                        ni.node.spec.taints, pod.spec.tolerations,
                        ("NoSchedule", "NoExecute")) is None
                ok[idx] = good
            return ok

        for r, u in enumerate(rows):
            info = self.rows[u] if u < len(self.rows) else None
            if info is None:
                continue
            pod = info.pod
            # spread filter
            if info.f_constraints:
                keys = [c.topology_key for c in info.f_constraints]
                for j, c in enumerate(info.f_constraints):
                    out["spr_f_tv"][r, j] = tv_vec(c.topology_key)
                    out["spr_f_dom"][r, j] = dom_of_key(c.topology_key)
                    out["spr_f_elig"][r, j] = elig_vec(c, pod, keys)
            # spread score
            if info.s_constraints:
                keys = [c.topology_key for c in info.s_constraints]
                out["spr_s_keys_ok"][r] = keys_ok_vec(keys)
                for j, c in enumerate(info.s_constraints):
                    out["spr_s_tv"][r, j] = tv_vec(c.topology_key)
                    out["spr_s_dom"][r, j] = dom_of_key(c.topology_key)
                    out["spr_s_elig"][r, j] = elig_vec(c, pod, keys)
            # inter-pod affinity term topology values
            for t, term in enumerate(info.req_a):
                out["ipa_ra_tv"][r, t] = tv_vec(term.topology_key)
                out["ipa_ra_dom"][r, t] = dom_of_key(term.topology_key)
            for t, term in enumerate(info.req_aa):
                out["ipa_raa_tv"][r, t] = tv_vec(term.topology_key)
                out["ipa_raa_dom"][r, t] = dom_of_key(term.topology_key)
            for t, (term, _w) in enumerate(info.stc_terms):
                out["ipa_stc_tv"][r, t] = tv_vec(term.topology_key)
                out["ipa_stc_dom"][r, t] = dom_of_key(term.topology_key)
            for t, (term, _w) in enumerate(info.stp_terms):
                out["ipa_stp_tv"][r, t] = tv_vec(term.topology_key)
                out["ipa_stp_dom"][r, t] = dom_of_key(term.topology_key)
        return out

    # -- count seeding --------------------------------------------------------

    def seed_counts(self, snapshot, rows: range, nis=None):
        """Count arrays for the given rows from the LIVE snapshot, computed
        by running the host plugins' PreFilter/PreScore on the representative
        pod — the device then carries these forward incrementally."""
        from ..framework.interface import CycleState
        from ..plugins import interpodaffinity as ipa_mod
        from ..plugins import podtopologyspread as pts_mod

        d = self.dims
        st = self.state
        N = st.dims.nodes
        SC, TA, TAA = d.spread_constraints, d.ipa_req_terms, d.ipa_anti_terms
        R = len(rows)
        out = dict(
            spr_f_cnt=np.zeros((R, SC, N), np.int32),
            spr_f_min_zero=np.zeros((R, SC), bool),
            spr_s_cnt=np.zeros((R, SC, N), np.int32),
            ipa_veto=np.zeros((R, N), np.int32),
            ipa_a_cnt=np.zeros((R, TA, N), np.int32),
            ipa_a_total=np.zeros((R,), np.int64),
            ipa_aa_cnt=np.zeros((R, TAA, N), np.int32),
            ipa_score=np.zeros((R, N), np.int64),
        )
        node_list = snapshot.node_info_list
        if nis is None:
            nis = self._node_rows(snapshot)
        # the count surfaces are still computed by the host plugins' own
        # PreFilter/PreScore (shared-code parity contract, class doc) —
        # but the per-NODE scatter of every count map now rides the
        # columnar label store: one sorted-search gather over interned
        # topology-value ids per (row, constraint/term) instead of an
        # O(nodes) Python dict-probe walk per signature
        from ..ingest.groupcols import gather_ids
        cols = self.cols.sync(nis)

        for r, u in enumerate(rows):
            info = self.rows[u] if u < len(self.rows) else None
            if info is None:
                continue
            pod = info.pod
            # spread DoNotSchedule counts via the plugin's own PreFilter
            if info.f_constraints:
                cs = CycleState()
                self.pts.pre_filter(cs, pod, node_list)
                s = cs.read_or_none(pts_mod._PRE_FILTER_KEY)
                if s is not None:
                    for j, c in enumerate(s.constraints):
                        cnts = s.tp_value_to_match_num[j]
                        out["spr_f_min_zero"][r, j] = len(cnts) < c.min_domains
                        if not any(cnts.values()):
                            continue    # all-zero seed: the array is zeros
                        out["spr_f_cnt"][r, j] = gather_ids(
                            cols.tv(c.topology_key),
                            cols.value_ids(c.topology_key, cnts), np.int32)
            # spread ScheduleAnyway counts: hostname keys per node, others
            # accumulated per topology value over count-eligible nodes
            for j, c in enumerate(info.s_constraints):
                if c.topology_key == LABEL_HOSTNAME:
                    for idx, ni in nis:
                        out["spr_s_cnt"][r, j, idx] = \
                            pts_mod._count_pods_match_selector(
                                ni.pods, c.selector, pod.namespace)
                    continue
                keys = [cc.topology_key for cc in info.s_constraints]
                by_tv: dict[str, int] = {}
                for idx, ni in nis:
                    labels = ni.node.metadata.labels
                    if not all(k in labels for k in keys):
                        continue
                    if not pts_mod._match_node_inclusion_policies(c, pod, ni):
                        continue
                    v = labels[c.topology_key]
                    by_tv[v] = by_tv.get(v, 0) + \
                        pts_mod._count_pods_match_selector(
                            ni.pods, c.selector, pod.namespace)
                if not any(by_tv.values()):
                    continue
                out["spr_s_cnt"][r, j] = gather_ids(
                    cols.tv(c.topology_key),
                    cols.value_ids(c.topology_key, by_tv), np.int32)
            with self.ipa_phase():
                # inter-pod affinity maps via the plugin's PreFilter. Empty
                # count maps (the common fresh-workload case) skip their
                # gathers outright — the arrays are zeros.
                cs = CycleState()
                self.ipa.pre_filter(cs, pod, node_list)
                s = cs.read_or_none(ipa_mod._PRE_FILTER_KEY)
                if s is not None:
                    out["ipa_a_total"][r] = sum(s.affinity_counts.values())
                    if s.existing_anti_affinity_counts:
                        # counts keyed (label key, value): a node contributes
                        # each (k, v) it carries — per distinct k, one gather
                        by_key: dict = {}
                        for (lk, lv), c0 in \
                                s.existing_anti_affinity_counts.items():
                            by_key.setdefault(lk, {})[lv] = c0
                        veto = out["ipa_veto"][r]
                        for lk, vals in by_key.items():
                            veto += gather_ids(cols.tv(lk),
                                               cols.value_ids(lk, vals),
                                               np.int32)
                    if s.affinity_counts:
                        by_key = {}
                        for (tk, tv), c0 in s.affinity_counts.items():
                            by_key.setdefault(tk, {})[tv] = c0
                        for t, term in enumerate(info.req_a):
                            vals = by_key.get(term.topology_key)
                            if vals:
                                out["ipa_a_cnt"][r, t] = gather_ids(
                                    cols.tv(term.topology_key),
                                    cols.value_ids(term.topology_key, vals),
                                    np.int32)
                    if s.anti_affinity_counts:
                        by_key = {}
                        for (tk, tv), c0 in s.anti_affinity_counts.items():
                            by_key.setdefault(tk, {})[tv] = c0
                        for t, term in enumerate(info.req_aa):
                            vals = by_key.get(term.topology_key)
                            if vals:
                                out["ipa_aa_cnt"][r, t] = gather_ids(
                                    cols.tv(term.topology_key),
                                    cols.value_ids(term.topology_key, vals),
                                    np.int32)
                # symmetric score surface via the plugin's PreScore
                cs = CycleState()
                self.ipa.pre_score(cs, pod, node_list, all_nodes=node_list)
                ps = cs.read_or_none(ipa_mod._PRE_SCORE_KEY)
                if ps is not None and ps.topology_score:
                    score = out["ipa_score"][r]
                    for tk, tv_scores in ps.topology_score.items():
                        score += gather_ids(cols.tv(tk),
                                            cols.value_ids(tk, tv_scores),
                                            np.int64)
        return out

    # -- assembly -------------------------------------------------------------

    def families(self, snapshot) -> GroupFamilies:
        """Host-side activation analysis (no device readbacks): a family is
        active when some signature row carries it, or — for the symmetric
        inter-pod families — when existing cluster pods seed its counts."""
        return GroupFamilies(
            spr_f=bool(self.spr_f_active.any()),
            spr_s=bool(self.spr_s_active.any()),
            ipa_req=bool(self.ipa_ra_active.any()),
            ipa_anti=bool(
                self.ipa_raa_active.any() or self.m_ipa_exist.any()
                or snapshot.have_pods_with_required_anti_affinity_list),
            ipa_score=bool(
                self.w_stc.any() or self.w_stp.any()
                or snapshot.have_pods_with_affinity_list
                or snapshot.have_pods_with_required_anti_affinity_list),
        )

    def device_rows(self) -> int:
        """Row-axis size of the DEVICE group tensors: the padded count of
        rows that actually exist, not the table's full padded capacity —
        a one-signature spread workload ships [2, SC, N] tensors instead
        of [16, SC, N], cutting every per-step group op by the same
        factor. Crossing a pow2 boundary changes the capacity key, which
        triggers a full reseed (the scheduler's _gd_capacity check)."""
        from ..state.tensorize import pow2_at_least
        return min(pow2_at_least(max(len(self.rows), 1), 2), self.U)

    def build_dev(self, snapshot) -> "tuple[GroupsDev, GroupCarry]":
        """Full (GroupsDev, GroupCarry) numpy build for all rows."""
        rows = range(len(self.rows))
        nis = self._node_rows(snapshot)
        nd = self.node_data(snapshot, rows, nis=nis)
        seeds = self.seed_counts(snapshot, rows, nis=nis)
        U, N = self.device_rows(), self.state.dims.nodes
        d = self.dims

        def full(name, shape, dtype):
            arr = np.zeros(shape, dtype)
            src = nd.get(name) if name in nd else seeds.get(name)
            arr[:src.shape[0]] = src
            return arr

        # host-owned per-row / pairwise fields slice via the SAME field
        # lists grow() and scatter_new_rows use — one classification source
        sliced = {name: getattr(self, name)[:U].copy()
                  for name in self._ROW_FIELDS}
        sliced.update({name: getattr(self, name)[:U, :U].copy()
                       for name in self._PAIRWISE_FIELDS})
        gd = GroupsDev(
            spr_f_tv=full("spr_f_tv", (U, d.spread_constraints, N), np.int32),
            spr_f_elig=full("spr_f_elig", (U, d.spread_constraints, N), bool),
            spr_f_dom=full("spr_f_dom", (U, d.spread_constraints, N), np.int32),
            spr_s_tv=full("spr_s_tv", (U, d.spread_constraints, N), np.int32),
            spr_s_elig=full("spr_s_elig", (U, d.spread_constraints, N), bool),
            spr_s_keys_ok=full("spr_s_keys_ok", (U, N), bool),
            spr_s_dom=full("spr_s_dom", (U, d.spread_constraints, N), np.int32),
            ipa_ra_tv=full("ipa_ra_tv", (U, d.ipa_req_terms, N), np.int32),
            ipa_ra_dom=full("ipa_ra_dom", (U, d.ipa_req_terms, N), np.int32),
            ipa_raa_tv=full("ipa_raa_tv", (U, d.ipa_anti_terms, N), np.int32),
            ipa_raa_dom=full("ipa_raa_dom", (U, d.ipa_anti_terms, N), np.int32),
            ipa_stc_tv=full("ipa_stc_tv", (U, d.ipa_cons_terms, N), np.int32),
            ipa_stc_dom=full("ipa_stc_dom", (U, d.ipa_cons_terms, N), np.int32),
            ipa_stp_tv=full("ipa_stp_tv", (U, d.ipa_plcd_terms, N), np.int32),
            ipa_stp_dom=full("ipa_stp_dom", (U, d.ipa_plcd_terms, N), np.int32),
            **sliced,
        )
        gc = GroupCarry(
            spr_f_cnt=full("spr_f_cnt", (U, d.spread_constraints, N), np.int32),
            spr_f_min_zero=full("spr_f_min_zero", (U, d.spread_constraints), bool),
            spr_s_cnt=full("spr_s_cnt", (U, d.spread_constraints, N), np.int32),
            ipa_veto=full("ipa_veto", (U, N), np.int32),
            ipa_a_cnt=full("ipa_a_cnt", (U, d.ipa_req_terms, N), np.int32),
            ipa_a_total=full("ipa_a_total", (U,), np.int64),
            ipa_aa_cnt=full("ipa_aa_cnt", (U, d.ipa_anti_terms, N), np.int32),
            ipa_score=full("ipa_score", (U, N), np.int64),
        )
        return gd, gc


def to_device(tree):
    """numpy → jnp leaves of a GroupsDev / GroupCarry."""
    import jax.numpy as jnp
    from ..perf.ledger import GLOBAL as _ledger
    _ledger.note_h2d_tree("host_group_seed", tree)
    return type(tree)(*(jnp.asarray(x) for x in tree))


def scatter_new_rows(gd_dev: GroupsDev, gc_dev: GroupCarry,
                     mgr: GroupManager, snapshot, lo: int, hi: int,
                     mesh=None):
    """Seed rows [lo, hi) into resident device group state: node-dependent
    tensors and counts scatter into the row slice; the small per-row scalars
    and pairwise matrices (which gained entries against OLD rows too) are
    re-uploaded whole.

    With `mesh`, the resident tensors are node-axis sharded
    (parallel/sharding.py): each update ships pre-sharded so the row
    scatter stays an in-place per-shard write instead of forcing a
    gather/reshard — the incremental path SURVEY §7.3 calls for, now
    first-class under multi-chip."""
    import jax
    import jax.numpy as jnp

    rows = range(lo, hi)
    U = gd_dev.spr_f_active.shape[0]   # device row axis (compact, pow2)
    nis = mgr._node_rows(snapshot)
    nd = mgr.node_data(snapshot, rows, nis=nis)
    seeds = mgr.seed_counts(snapshot, rows, nis=nis)

    def put(update, like):
        if mesh is None:
            return jnp.asarray(update)
        return jax.device_put(update, like.sharding)

    gd_kw = {name: getattr(gd_dev, name).at[lo:hi].set(
                 put(nd[name], getattr(gd_dev, name)))
             for name in nd}
    for name in GroupManager._ROW_FIELDS:
        gd_kw[name] = put(getattr(mgr, name)[:U], getattr(gd_dev, name))
    for name in GroupManager._PAIRWISE_FIELDS:
        gd_kw[name] = put(getattr(mgr, name)[:U, :U], getattr(gd_dev, name))
    gc_kw = {name: getattr(gc_dev, name).at[lo:hi].set(
                 put(seeds[name], getattr(gc_dev, name)))
             for name in seeds}
    return gd_dev._replace(**gd_kw), gc_dev._replace(**gc_kw)


# ---------------------------------------------------------------------------
# wave fold: batch-apply a wave's accepted placements to the FULL carry
# (ops/program.py run_wave). Every group_update increment is a pure
# gated ADD, so the sequential per-placement updates commute — the whole
# wave folds into the carry with one scatter/gather pass per family
# instead of one [U, SC, N] update per placement.


def _dom_share(tv, dom, w, axis=None, n_seg=None):
    """Σ_m w[m] over nodes m sharing n's topology value (tv ≠ 0 both
    sides) — the "same-topology-value broadcast" of group_update, batched
    over placements via the dense dom ids. tv/dom: int [..., N]; w: int
    [..., N]; returns w's dtype [..., N].

    Sharded (`axis` set): the node dim is the LOCAL shard but `dom` holds
    GLOBAL dense domain ids, so the segment accumulator is widened to
    `n_seg` (a global bound) and all-reduced over `axis` before the
    gather-back — integer adds, so bit-identical to the single-device
    fold in any reduction order."""
    import jax
    import jax.numpy as jnp

    lead = tv.shape[:-1]
    n = tv.shape[-1]
    width = n if n_seg is None else n_seg
    tv2 = tv.reshape(-1, n)
    dom2 = dom.reshape(-1, n)
    w2 = w.reshape(-1, n)

    def one(t, d, x):
        seg = jnp.zeros((width,), x.dtype).at[d].add(jnp.where(t != 0, x, 0))
        if axis is not None:
            seg = jax.lax.psum(seg, axis)
        return jnp.where(t != 0, seg[d], 0)

    return jax.vmap(one)(tv2, dom2, w2).reshape(*lead, n)


def wave_fold(gd: GroupsDev, gc: GroupCarry, wt, cnt_sn,
              fam: Optional[GroupFamilies] = None,
              axis=None, n_seg=None) -> GroupCarry:
    """GroupCarry after a wave: `wt` i32 [S] are the wave's table rows and
    `cnt_sn` i32 [S, N] the accepted placement counts of each wave row per
    node. Exactly equals folding the placements through group_update one
    by one, in any order (additivity; node labels static).

    Sharded (`axis` set, `n_seg` = global node bound): node-last inputs
    are local shards; domain shares are all-reduced inside `_dom_share`
    and the replicated `a_total` scalar sum is psum'd, so the per-node
    carry shards fold exactly as the single-device path does."""
    import jax
    import jax.numpy as jnp

    fam = fam or ALL_FAMILIES
    spr_f_cnt, spr_s_cnt = gc.spr_f_cnt, gc.spr_s_cnt
    ipa_veto, ipa_a_cnt = gc.ipa_veto, gc.ipa_a_cnt
    ipa_a_total, ipa_aa_cnt = gc.ipa_a_total, gc.ipa_aa_cnt
    ipa_score = gc.ipa_score
    cnt32 = cnt_sn.astype(jnp.int32)

    if fam.spr_f:
        # per (consumer u, constraint c): weights at the PLACED node m are
        # Σ_s m_spr_f[placed s → u, c] · cnt[s, m], gated by u's count
        # eligibility of m; shared to every node in m's topology domain
        w_ucn = jnp.einsum("suc,sn->ucn", gd.m_spr_f[wt].astype(jnp.int32),
                           cnt32)
        add = _dom_share(gd.spr_f_tv, gd.spr_f_dom,
                         w_ucn * gd.spr_f_elig, axis, n_seg)
        spr_f_cnt = gc.spr_f_cnt + add

    if fam.spr_s:
        w_ucn = jnp.einsum("suc,sn->ucn", gd.m_spr_s[wt].astype(jnp.int32),
                           cnt32)
        topo = _dom_share(gd.spr_s_tv, gd.spr_s_dom,
                          w_ucn * gd.spr_s_elig, axis, n_seg)
        # hostname constraints count the chosen node's own pods, no
        # eligibility gate (group_update's is_host branch)
        spr_s_cnt = gc.spr_s_cnt + jnp.where(
            gd.spr_s_is_host[:, :, None], w_ucn, topo)

    if fam.ipa_anti:
        # existing-anti veto: shared along the PLACED row's term topology
        raa_tv_w = gd.ipa_raa_tv[wt]                       # [S, TAA, N]
        raa_dom_w = gd.ipa_raa_dom[wt]
        shared_st = _dom_share(
            raa_tv_w, raa_dom_w,
            jnp.broadcast_to(cnt32[:, None, :], raa_tv_w.shape),
            axis, n_seg)
        ipa_veto = gc.ipa_veto + jnp.einsum(
            "sut,stn->un", gd.m_ipa_exist[wt].astype(jnp.int32), shared_st)
        # incoming-anti counts: shared along the CONSUMER's term topology
        w_utn = jnp.einsum("sut,sn->utn", gd.m_ipa_aa[wt].astype(jnp.int32),
                           cnt32)
        ipa_aa_cnt = gc.ipa_aa_cnt + _dom_share(
            gd.ipa_raa_tv, gd.ipa_raa_dom, w_utn, axis, n_seg)

    if fam.ipa_req:
        w_un = jnp.einsum("su,sn->un", gd.m_ipa_a[wt].astype(jnp.int32),
                          cnt32)
        ipa_a_cnt = gc.ipa_a_cnt + _dom_share(
            gd.ipa_ra_tv, gd.ipa_ra_dom,
            w_un[:, None, :] * gd.ipa_ra_active[:, :, None], axis, n_seg)
        # a_total: each placement adds (# active consumer terms whose
        # topology key exists on the placed node) when it matches all of
        # the consumer's terms (group_update's tvb_a != 0 gate)
        k_un = jnp.sum(gd.ipa_ra_active[:, :, None]
                       & (gd.ipa_ra_tv != 0), axis=1)     # [U, N]
        # a TPU has no 64-bit integer dot: int64 contractions in this
        # fold multiply and sum instead of einsum
        a_add = jnp.sum(w_un.astype(jnp.int64) * k_un.astype(jnp.int64),
                        axis=1)
        if axis is not None:
            a_add = jax.lax.psum(a_add, axis)
        ipa_a_total = gc.ipa_a_total + a_add

    if fam.ipa_score:
        # the score weights are int64 (no dot on a TPU: multiply and sum)
        cnt64 = cnt_sn.astype(jnp.int64)
        # consumer-side preferred terms matching the placed pod
        wc_utn = jnp.sum(gd.w_stc[wt][:, :, :, None]
                         * cnt64[:, None, None, :], axis=0)     # [U, CT, N]
        cons_add = jnp.sum(_dom_share(gd.ipa_stc_tv, gd.ipa_stc_dom,
                                      wc_utn, axis, n_seg), axis=1)    # [U, N]
        # placed-side terms: share counts along the placed row's term
        # topology, then weight per consumer
        stp_tv_w = gd.ipa_stp_tv[wt]                       # [S, PT, N]
        stp_dom_w = gd.ipa_stp_dom[wt]
        shared_p = _dom_share(
            stp_tv_w, stp_dom_w,
            jnp.broadcast_to(cnt64[:, None, :], stp_tv_w.shape),
            axis, n_seg)
        plcd_add = jnp.sum(gd.w_stp[wt][:, :, :, None]
                           * shared_p[:, None, :, :], axis=(0, 2))  # [U, N]
        ipa_score = gc.ipa_score + cons_add + plcd_add

    return GroupCarry(spr_f_cnt=spr_f_cnt, spr_f_min_zero=gc.spr_f_min_zero,
                      spr_s_cnt=spr_s_cnt, ipa_veto=ipa_veto,
                      ipa_a_cnt=ipa_a_cnt, ipa_a_total=ipa_a_total,
                      ipa_aa_cnt=ipa_aa_cnt, ipa_score=ipa_score)
