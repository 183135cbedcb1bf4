"""Node-axis sharding: the scheduler's long axis distributed over a device mesh.

The reference scales its node axis with adaptive sampling + √n-chunked
parallel iteration (SURVEY §2.6); the TPU design shards the node axis of the
tensorized cluster state over a `jax.sharding.Mesh` instead. Every filter and
score kernel in ops/program.py is row-independent over nodes, so the per-pod
evaluation runs unchanged on each shard; only the argmax and the carry update
need cross-device communication:

  local masked-score → local argmax → `lax.pmax` of the best score →
  `lax.pmin` of the global index among shards holding that score (this
  reproduces the single-device "first max index" tie-break exactly) →
  each shard applies the placement only if the winning row is local.
  (`ops/collectives.py`: a 64-bit max/min is an all-gather plus a local
  reduction — the TPU lowers only Sum for 64-bit all-reduces.)

Two scalar collectives per pod step, riding ICI — plus, when group kernels
(PodTopologySpread / InterPodAffinity, ops/groups.py) are active:
  - `pmin` for the global minimum match count across domains,
  - a psum'd domain-flag vector for the global distinct-domain count,
  - pmax/pmin scalars for the score normalizations, and
  - a psum broadcast of the chosen node's topology values so every shard can
    apply the same-topology-value count update to its local slice.

The assignments stream is replicated; the carry stays sharded.
`run_batch_sharded` therefore returns bit-identical assignments to
`ops.program.run_batch` (asserted in tests/test_sharding.py) while holding
1/D of the node state per device — the "long-context" scaling story of
SURVEY §5.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from ..ops.collectives import pmax, pmin
from ..ops.groups import GroupCarry, GroupsDev, group_update
from ..ops.program import (MAX_SCORE, Carry, PodRow, PodTableDev, PodXs,
                           ScoreConfig, SigCache, _apply_assignment,
                           _eval_pod, _fit_scores, _gather_row, _row_refresh,
                           _sum_terms, _uniform_matrix, _WaveState,
                           balanced_allocation, default_normalize, fit_mask,
                           least_allocated, ports_mask)
from ..perf.ledger import GLOBAL as LEDGER, device_entry
from ..state.tensorize import NodeArrays

NODE_AXIS = "nodes"

def _shard_map(f, mesh, in_specs, out_specs):
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


_INT_MAX = jnp.iinfo(jnp.int32).max

# the signature-cache sig is a replicated scalar; every other carry leaf is
# sharded along the node axis
_CACHE_SPEC = SigCache(sig=P(), static_mask=P(NODE_AXIS), taint_raw=P(NODE_AXIS),
                       s_img=P(NODE_AXIS),
                       na_raw=P(NODE_AXIS), fit_ok=P(NODE_AXIS),
                       s_fit=P(NODE_AXIS), s_bal=P(NODE_AXIS))

# group tensors: node axis is the LAST dim of the node-indexed arrays; the
# per-row scalars and pairwise match matrices are replicated
_GD_NODE_FIELDS = ("spr_f_tv", "spr_f_elig", "spr_f_dom", "spr_s_tv",
                   "spr_s_elig", "spr_s_keys_ok", "spr_s_dom", "ipa_ra_tv",
                   "ipa_ra_dom", "ipa_raa_tv", "ipa_raa_dom", "ipa_stc_tv",
                   "ipa_stc_dom", "ipa_stp_tv", "ipa_stp_dom")
_GC_NODE_FIELDS = ("spr_f_cnt", "spr_s_cnt", "ipa_veto", "ipa_a_cnt",
                   "ipa_aa_cnt", "ipa_score")


def _last_axis_spec(tree, node_fields):
    def spec(name, arr):
        if name in node_fields:
            return P(*([None] * (np_ndim(arr) - 1) + [NODE_AXIS]))
        return P()
    return type(tree)(**{name: spec(name, getattr(tree, name))
                         for name in tree._fields})


def np_ndim(x) -> int:
    return getattr(x, "ndim", 0)


def _carry_spec(carry: Carry) -> Carry:
    groups_spec = None
    if carry.groups is not None:
        groups_spec = _last_axis_spec(carry.groups, _GC_NODE_FIELDS)
    return Carry(used=P(NODE_AXIS), nonzero_used=P(NODE_AXIS),
                 npods=P(NODE_AXIS), ports=P(NODE_AXIS), cache=_CACHE_SPEC,
                 groups=groups_spec)


def make_mesh(n_devices: int | None = None, devices=None) -> Mesh:
    """1-D mesh over the node axis."""
    if devices is None:
        devices = jax.devices()[: n_devices or len(jax.devices())]
    import numpy as np
    return Mesh(np.array(devices), (NODE_AXIS,))


def _sharded_step(cfg: ScoreConfig, axis: str, n_global: int,
                  na_l: NodeArrays, table: PodTableDev,
                  groups: GroupsDev | None, offset: jnp.ndarray, fam,
                  c: Carry, x: PodXs):
    """One pod placement on a node shard. Collectives: pmax + pmin (plus the
    global normalization maxes inside _eval_pod and the group-kernel
    collectives described in the module docstring)."""
    n_local = na_l.cap.shape[0]
    pod = _gather_row(table, x)
    mask, score, parts = _eval_pod(cfg, na_l, c, pod, axis=axis,
                                   groups=groups, tidx=x.tidx,
                                   n_global=n_global, fam=fam)
    masked = jnp.where(mask, score, -1)
    lbest = jnp.argmax(masked).astype(jnp.int32)
    lscore = masked[lbest]
    gscore = pmax(lscore, axis)
    # global "first max index" tie-break == single-device argmax semantics
    cand = jnp.where(lscore == gscore, offset + lbest, _INT_MAX)
    gbest = pmin(cand, axis)
    assigned = (gscore >= 0) & pod.valid
    lidx = gbest - offset
    in_shard = (lidx >= 0) & (lidx < n_local)
    lidx_safe = jnp.clip(lidx, 0, n_local - 1).astype(jnp.int32)
    gate = assigned & in_shard
    c2 = _apply_assignment(c, pod, lidx_safe, gate)
    c2 = c2._replace(cache=_row_refresh(cfg, na_l, c2, pod, lidx_safe,
                                        gate, parts))
    if groups is not None:
        def pick(arr):
            # chosen node's value, broadcast from the owning shard
            local = arr[..., lidx_safe]
            return lax.psum(jnp.where(in_shard, local,
                                      jnp.zeros_like(local)), axis)

        is_chosen = in_shard & (jnp.arange(n_local, dtype=jnp.int32)
                                == lidx_safe)
        # gate here is GLOBAL placement (counts update on every shard's
        # local slice via topology-value sharing)
        c2 = c2._replace(groups=group_update(groups, c2.groups, x.tidx,
                                             pick, is_chosen, assigned,
                                             fam=fam))
    return c2, jnp.where(assigned, gbest, -1)


@functools.partial(jax.jit, static_argnames=("cfg", "mesh", "fam"))
def _run_batch_sharded_jit(cfg: ScoreConfig, mesh: Mesh, na: NodeArrays,
                           carry: Carry, pods: PodXs, table: PodTableDev,
                           groups: GroupsDev | None = None, fam=None):
    """`ops.program.run_batch` with the node axis sharded over `mesh`.

    N (the padded node count) must be divisible by the mesh size; the
    pow-of-two padding of ClusterState guarantees this for pow-of-two
    meshes. Returns (final sharded carry, replicated assignments[B]).
    """
    n_global = na.cap.shape[0]
    node_sharded_na = NodeArrays(*(P(NODE_AXIS) for _ in na))
    node_sharded_carry = _carry_spec(carry)
    # optional leaves (nom_idx=None — overlays are single-device-only)
    # keep their None spec: a P() over a None leaf breaks tree matching
    replicated_pods = PodXs(*(P() if x is not None else None for x in pods))
    replicated_table = PodTableDev(*(P() for _ in table))
    groups_spec = (_last_axis_spec(groups, _GD_NODE_FIELDS)
                   if groups is not None else None)

    def local(na_l: NodeArrays, carry_l: Carry, pods_r: PodXs,
              table_r: PodTableDev, groups_l):
        n_local = na_l.cap.shape[0]
        offset = (lax.axis_index(NODE_AXIS) * n_local).astype(jnp.int32)
        step = functools.partial(_sharded_step, cfg, NODE_AXIS, n_global,
                                 na_l, table_r, groups_l, offset, fam)
        return lax.scan(step, carry_l, pods_r)

    fn = _shard_map(
        local, mesh=mesh,
        in_specs=(node_sharded_na, node_sharded_carry, replicated_pods,
                  replicated_table, groups_spec),
        out_specs=(node_sharded_carry, P()))
    return fn(na, carry, pods, table, groups)


@device_entry(cost=(48.0, 4.0))
def run_batch_sharded(cfg: ScoreConfig, mesh: Mesh, na: NodeArrays,
                      carry: Carry, pods: PodXs, table: PodTableDev,
                      groups: GroupsDev | None = None, fam=None):
    """Ledger-instrumented entry for `_run_batch_sharded_jit` (compile
    ledger: perf/ledger.py — the sharded program's compiles are the
    expensive ones, one executable per mesh shape). Host-side per-pod
    inputs are explicitly staged like every single-device entry, so the
    mesh path runs under the sanitizer rails' ambient transfer guard
    too (ISSUE 10 satellite: run_batch_sharded was the only JIT entry
    outside the rails/ledger coverage)."""
    return _run_batch_sharded_jit, (cfg, mesh, na, carry, pods, table,
                                    groups, fam)


@functools.partial(jax.jit, static_argnames=("cfg", "fam"))
def _lane_probe_jit(cfg: ScoreConfig, na_l: NodeArrays, carry_l: Carry,
                    pods: PodXs, table: PodTableDev, fam=None):
    """One lane's LOCAL compute, collectives elided: the same per-pod
    eval/argmax/carry-update scan `_sharded_step` runs on each shard,
    minus the pmax/pmin exchange. Timing this per lane against the full
    sharded program's blocked wall is what decomposes the mesh gap into
    compute vs comms (ROADMAP item 1): the slowest lane bounds the
    compute share, the remainder is collectives + dispatch."""
    n_local = na_l.cap.shape[0]

    def step(c, x):
        pod = _gather_row(table, x)
        mask, score, parts = _eval_pod(cfg, na_l, c, pod, axis=None,
                                       groups=None, tidx=x.tidx,
                                       n_global=n_local, fam=fam)
        masked = jnp.where(mask, score, -1)
        best = jnp.argmax(masked).astype(jnp.int32)
        gate = (masked[best] >= 0) & pod.valid
        c2 = _apply_assignment(c, pod, best, gate)
        c2 = c2._replace(cache=_row_refresh(cfg, na_l, c2, pod, best,
                                            gate, parts))
        return c2, jnp.where(gate, best, -1)

    return lax.scan(step, carry_l, pods)


def _lane_carry(host_carry: Carry, sl: slice) -> Carry:
    """Slice the node axis of a host copy of the carry (groups must be
    None — the lane probe is group-free)."""
    cache = host_carry.cache
    cache_l = type(cache)(
        sig=cache.sig,
        **{f: getattr(cache, f)[sl] for f in cache._fields if f != "sig"})
    return Carry(used=host_carry.used[sl],
                 nonzero_used=host_carry.nonzero_used[sl],
                 npods=host_carry.npods[sl],
                 ports=host_carry.ports[sl], cache=cache_l, groups=None)


# lane-imbalance verdict threshold (ISSUE 20): peak/mean lane time
# beyond this means a straggler lane binds the collective barrier
IMBALANCE_BOUND_RATIO = 1.5


def profile_shard_lanes(cfg: ScoreConfig, mesh: Mesh, na: NodeArrays,
                        carry: Carry, pods: PodXs, table: PodTableDev,
                        groups: GroupsDev | None = None, fam=None) -> dict:
    """Sharded-lane profile (ISSUE 14): per-device local-compute seconds,
    time imbalance, and an all-reduce/comms share estimate for
    `run_batch_sharded` — the decomposition ROADMAP item 1 needs before
    porting the single-device toolchain onto the mesh.

    Measurement harness, NOT hot path: re-dispatches the (non-donating)
    sharded program on the given inputs with a blocking fence for the
    total wall, then times each lane's node slice through the group-free
    local scan (`_lane_probe_jit` — one executable for all lanes, they
    share a shape). `commsShare` attributes everything the slowest lane
    does not explain to collectives + dispatch; `imbalanceRatio` is
    max/mean over lanes. Transfers use the explicit device_get/device_put
    escapes so the sanitizer rails' guard stays honest. When group
    kernels are active only the total is measured (the local scan has no
    group-collective twin) and `skipped` says why."""
    import time as _t

    import numpy as np

    n_dev = int(mesh.devices.size)

    def run_full():
        out = _run_batch_sharded_jit(cfg, mesh, na, carry, pods, table,
                                     groups, fam)
        jax.block_until_ready(out)

    run_full()    # warm — a no-op re-dispatch when the drain already ran
    t0 = _t.perf_counter()
    run_full()
    total = _t.perf_counter() - t0
    prof = {"nDevices": n_dev, "totalSeconds": round(total, 6),
            "laneSeconds": [], "imbalanceRatio": 0.0, "commsShare": 0.0,
            "pods": int(np.asarray(jax.device_get(pods.valid)).shape[0])}
    if groups is not None or carry.groups is not None:
        prof["skipped"] = "group kernels active: lane probe is group-free"
        return prof

    host = jax.tree_util.tree_map(
        np.asarray, jax.device_get((na, carry, pods, table)))
    host_na, host_carry, host_pods, host_table = host
    n_nodes = int(host_na.cap.shape[0])
    nl = n_nodes // n_dev
    prof["nodesPerLane"] = nl
    pods_d, table_d = jax.device_put((host_pods, host_table))
    lane_in = []
    for d in range(n_dev):
        sl = slice(d * nl, (d + 1) * nl)
        na_l = NodeArrays(*(np.ascontiguousarray(x[sl]) for x in host_na))
        lane_in.append(jax.device_put((na_l, _lane_carry(host_carry, sl))))
    # warm the (single, shared-shape) lane executable outside the timings
    jax.block_until_ready(
        _lane_probe_jit(cfg, lane_in[0][0], lane_in[0][1], pods_d, table_d,
                        fam=fam))
    lanes = []
    for na_l, carry_l in lane_in:
        t0 = _t.perf_counter()
        jax.block_until_ready(
            _lane_probe_jit(cfg, na_l, carry_l, pods_d, table_d, fam=fam))
        lanes.append(_t.perf_counter() - t0)
    mean = sum(lanes) / len(lanes)
    peak = max(lanes)
    prof["laneSeconds"] = [round(s, 6) for s in lanes]
    prof["imbalanceRatio"] = round(peak / mean, 4) if mean > 0 else 0.0
    prof["commsShare"] = (round(max(0.0, 1.0 - peak / total), 4)
                          if total > 0 else 0.0)
    # lane verdict (ISSUE 20): classify what binds the sharded dispatch,
    # with the same comms threshold the device cost model uses — so the
    # lane profile, the kernel cost rows and the per-drain critical-path
    # chain all call the same dispatch "comms_bound" at the same share
    from ..perf.costmodel import COMMS_BOUND_SHARE
    prof["laneShares"] = ([round(s / total, 4) for s in lanes]
                          if total > 0 else [0.0] * len(lanes))
    if prof["commsShare"] > COMMS_BOUND_SHARE:
        prof["verdict"] = "comms_bound"
    elif prof["imbalanceRatio"] > IMBALANCE_BOUND_RATIO:
        # one straggler lane holds the collective barrier: the fix is
        # rebalancing the node slices, not shrinking the collectives
        prof["verdict"] = "imbalance_bound"
    else:
        prof["verdict"] = "compute_bound"
    return prof


def _note_shard_upload(phase: str, tree) -> None:
    """Attribute a mesh placement's H2D bytes to its drain phase — the
    same `scheduler_h2d_bytes_total{phase}` surface the single-device
    uploads report through (perf/ledger.py)."""
    LEDGER.note_h2d_tree(phase, tree)


def shard_node_arrays(mesh: Mesh, na: NodeArrays) -> NodeArrays:
    """Place the staging arrays onto the mesh, node axis split."""
    spec = NamedSharding(mesh, P(NODE_AXIS))
    out = NodeArrays(*(jax.device_put(jnp.asarray(x), spec) for x in na))
    _note_shard_upload("host_snapshot", out)
    return out


def shard_groups(mesh: Mesh, gd: GroupsDev) -> GroupsDev:
    """Place group static tensors: node-indexed arrays split, rest replicated."""
    out = {}
    for name in gd._fields:
        arr = jnp.asarray(getattr(gd, name))
        if name in _GD_NODE_FIELDS:
            spec = NamedSharding(mesh, P(*([None] * (arr.ndim - 1) + [NODE_AXIS])))
        else:
            spec = NamedSharding(mesh, P())
        out[name] = jax.device_put(arr, spec)
    gd = GroupsDev(**out)
    _note_shard_upload("host_group_seed", gd)
    return gd


def shard_group_carry(mesh: Mesh, gc: GroupCarry) -> GroupCarry:
    out = {}
    for name in gc._fields:
        arr = jnp.asarray(getattr(gc, name))
        if name in _GC_NODE_FIELDS:
            spec = NamedSharding(mesh, P(*([None] * (arr.ndim - 1) + [NODE_AXIS])))
        else:
            spec = NamedSharding(mesh, P())
        out[name] = jax.device_put(arr, spec)
    gc = GroupCarry(**out)
    _note_shard_upload("host_group_seed", gc)
    return gc


# ---------------------------------------------------------------------------
# ISSUE 16: the drain toolchain on the mesh. Four entries port the
# single-device fast paths onto the node-sharded mesh with exact bind
# parity (tests/test_sharded_parity.py):
#
#   run_uniform_sharded   closed-form top-L runs — ONE dispatch and ~O(1)
#                         collectives per span instead of 2 scalar
#                         collectives per pod (the BENCH_r09 20× gap was
#                         per-pod pmax/pmin latency, not bandwidth)
#   run_plan_sharded      the DrainCompiler's wavescan program with the
#                         group counters as psum/all-reduces over the axis
#   run_gang_sharded      both gang tiers (closed-form + scan) with the
#                         all-or-nothing verdict replicated
#   scatter_rows_sharded  dirty-row upload onto the resident mesh copy —
#                         the PR-9 columnar-ingest win for mesh drains
#
# Exactness of the sharded uniform merge: each shard evaluates its local
# top-K_loc candidates (K_loc = min(K, n_local); every member of the
# global top-K ranks inside its own shard's top-K_loc, so the union of
# local candidate sets contains the global candidate set), keys its
# [K_loc, J] score matrix with GLOBAL entry ids (node id · J + j), takes a
# local top-L_loc, and all-gathers (key, node) pairs for a replicated
# merge top-L. Keys are globally unique, so the merged top-L equals the
# single-device top-L of the full matrix whenever the run_uniform
# exactness preconditions hold — and when they fail, the replicated
# mono/norm/depth flags (pmin-reduced, conservative in the safe
# direction) send both paths to the identical-output scan.


def _uniform_local_core(cfg: ScoreConfig, n_global: int, L: int, K: int,
                        J: int, na_l: NodeArrays, carry_l: Carry, x: PodXs,
                        table: PodTableDev, n_actual):
    """SPMD body of the sharded closed-form run (shared with the gang
    uniform tier). `na_l`/`carry_l` are one node shard; `x`/`table`/
    `n_actual` replicated. Returns (local carry', replicated assignments
    i32[L], replicated exact/depth flags)."""
    n_local = na_l.cap.shape[0]
    n_dev = n_global // n_local
    K_loc = min(K, n_local)
    offset = (lax.axis_index(NODE_AXIS) * n_local).astype(jnp.int32)
    pod = _gather_row(table, x)
    feasible0, total0, parts = _eval_pod(cfg, na_l, carry_l, pod,
                                         axis=NODE_AXIS, n_global=n_global)
    masked0 = jnp.where(feasible0, total0, jnp.int64(-1))
    _, cand = lax.top_k(masked0.astype(jnp.int32), K_loc)
    cand = cand.astype(jnp.int32)

    # static per-node score components — globally normalized (axis), so
    # the matrix values match the single-device keys bit for bit
    s_taint = default_normalize(parts.taint_raw, feasible0, reverse=True,
                                axis=NODE_AXIS)
    s_na = default_normalize(parts.na_raw, feasible0, reverse=False,
                             axis=NODE_AXIS)
    static_add = (cfg.w_taint * s_taint + cfg.w_node_affinity * s_na
                  + cfg.w_image * parts.s_img)[cand]
    static_m = parts.static_mask[cand]
    norm_ok = (pmax(jnp.max(jnp.where(feasible0, parts.taint_raw, 0)),
                        NODE_AXIS) == 0) & (
        pmax(jnp.max(jnp.where(feasible0, parts.na_raw, 0)),
                 NODE_AXIS) == 0)

    fit_kj, s_fit_kj, s_bal_kj = _uniform_matrix(
        cfg, na_l, carry_l.used, carry_l.npods, carry_l.used,
        carry_l.nonzero_used, cand, pod, J)
    score_kj = (cfg.w_fit * s_fit_kj + cfg.w_balanced * s_bal_kj
                + static_add[:, None])
    masked_kj = jnp.where(static_m[:, None] & fit_kj, score_kj,
                          jnp.int64(-1))
    # checked over a SUPERSET of the single-device candidates — may only
    # be more conservative, and a False flag routes to the exact scan
    mono_ok = pmin(
        jnp.all(masked_kj[:, 1:] <= masked_kj[:, :-1]).astype(jnp.int32),
        NODE_AXIS) == 1

    score_max = MAX_SCORE * (cfg.w_fit + cfg.w_balanced + cfg.w_taint
                             + cfg.w_node_affinity + cfg.w_image)
    M = n_global * J
    key_dt = jnp.int32 if (score_max + 2) * M < 2 ** 31 else jnp.int64
    gcand = offset + cand
    ent_id = (gcand[:, None].astype(key_dt) * J
              + jnp.arange(J, dtype=key_dt)[None, :])
    flat_key = (masked_kj.astype(key_dt) * key_dt(M)
                - ent_id).reshape(K_loc * J)
    L_loc = min(L, K_loc * J)
    lvals, li = lax.top_k(flat_key, L_loc)
    node_l = gcand[(li // J).astype(jnp.int32)]
    g_vals = lax.all_gather(lvals, NODE_AXIS).reshape(n_dev * L_loc)
    g_node = lax.all_gather(node_l, NODE_AXIS).reshape(n_dev * L_loc)
    if g_vals.shape[0] < L:
        # defensive: the scheduler's shapes keep D·L_loc ≥ L; pad with
        # strictly-infeasible keys if a caller hands a thinner lattice
        pad = L - g_vals.shape[0]
        g_vals = jnp.concatenate(
            [g_vals, jnp.full((pad,), -key_dt(M) - 1, key_dt)])
        g_node = jnp.concatenate([g_node, jnp.full((pad,), -1, jnp.int32)])
    top_vals, top_i = lax.top_k(g_vals, L)
    node_of = g_node[top_i]
    sel_ok = (top_vals > -key_dt(M)) & (jnp.arange(L) < n_actual)
    assignments = jnp.where(sel_ok, node_of, -1).astype(jnp.int32)

    lid = assignments - offset
    in_shard = sel_ok & (lid >= 0) & (lid < n_local)
    lid_safe = jnp.clip(lid, 0, n_local - 1)
    counts_local = jnp.zeros((n_local,), jnp.int64).at[lid_safe].add(
        in_shard.astype(jnp.int64))
    counts = counts_local[cand]
    depth_ok = pmin(jnp.all(counts < J).astype(jnp.int32),
                        NODE_AXIS) == 1
    used = carry_l.used.at[cand].add(counts[:, None] * pod.req[None, :])
    nonzero = carry_l.nonzero_used.at[cand].add(
        counts[:, None] * pod.nonzero_req[None, :])
    npods = carry_l.npods.at[cand].add(counts.astype(carry_l.npods.dtype))

    # cache refresh at the local candidates: entry j=counts IS the
    # next-pod evaluation; untouched candidates write their pre-existing
    # value (fit_kj[k, 0] == parts at count 0), so the refreshed cache is
    # bit-identical to the single-device refresh
    ar = jnp.arange(K_loc)
    cnt_i = jnp.minimum(counts, J - 1).astype(jnp.int32)
    new_cache = SigCache(
        sig=pod.sig,
        static_mask=parts.static_mask, taint_raw=parts.taint_raw,
        na_raw=parts.na_raw, s_img=parts.s_img,
        fit_ok=parts.fit_ok.at[cand].set(fit_kj[ar, cnt_i]),
        s_fit=parts.s_fit.at[cand].set(s_fit_kj[ar, cnt_i]),
        s_bal=parts.s_bal.at[cand].set(s_bal_kj[ar, cnt_i]))
    new_carry = carry_l._replace(used=used, nonzero_used=nonzero,
                                 npods=npods, cache=new_cache)
    return new_carry, assignments, mono_ok & norm_ok, depth_ok


@functools.partial(jax.jit, static_argnames=("cfg", "mesh", "L", "K", "J"))
def _run_uniform_sharded_jit(cfg: ScoreConfig, mesh: Mesh, na: NodeArrays,
                             carry: Carry, x: PodXs, table: PodTableDev,
                             n_actual, L: int, K: int, J: int):
    n_global = na.cap.shape[0]
    node_na = NodeArrays(*(P(NODE_AXIS) for _ in na))
    carry_spec = _carry_spec(carry)
    x_spec = PodXs(*(P() if v is not None else None for v in x))
    table_spec = PodTableDev(*(P() for _ in table))

    def local(na_l, carry_l, x_r, table_r, n_act):
        return _uniform_local_core(cfg, n_global, L, K, J, na_l, carry_l,
                                   x_r, table_r, n_act)

    fn = _shard_map(local, mesh,
                    in_specs=(node_na, carry_spec, x_spec, table_spec, P()),
                    out_specs=(carry_spec, P(), P(), P()))
    new_carry, assignments, ok, depth_ok = fn(na, carry, x, table, n_actual)
    packed = jnp.concatenate([
        assignments, jnp.stack([ok, depth_ok]).astype(jnp.int32)])
    return new_carry, packed


@device_entry(cost=(32.0, 4.0))
def run_uniform_sharded(cfg: ScoreConfig, mesh: Mesh, na: NodeArrays,
                        carry: Carry, x: PodXs, table: PodTableDev,
                        n_actual, L: int, K: int, J: int):
    """`ops.program.run_uniform` on the mesh: the whole same-signature run
    is one dispatch with ~six collectives TOTAL (eval normalizations, the
    flag pmins, one all-gather merge) instead of two scalar collectives
    per pod — the flagship of the BENCH_r09 → r10 sharded-throughput fix.
    Packed layout identical to run_uniform ([assignments(L); exact;
    depth]); never donates — the scheduler keeps the input carry to
    replay failed exactness preconditions on the sharded scan."""
    return _run_uniform_sharded_jit, (cfg, mesh, na, carry, x, table,
                                      n_actual, L, K, J)


# ---------------------------------------------------------------------------
# the DrainCompiler's plan program on the mesh


def _plan_local(cfg: ScoreConfig, n_global: int, fam, norm_live: bool,
                has_groups: bool, has_ports: bool, na_l: NodeArrays,
                carry_l: Carry, xs, table: PodTableDev, wt, gd_l, statics_l):
    """SPMD body of `run_plan_sharded` — `ops.program._run_plan_impl`
    with the node axis local: per-signature surfaces and group counters
    hold one shard, the per-step argmax is the pmax/pmin global
    tie-break, and every "chosen node's row" read becomes an
    owner-broadcast psum. Serial order, conflict detection and the
    epilogue fold are unchanged, so assignments are bit-identical to the
    single-device plan program."""
    from ..ops.groups import GroupView, group_mask_view, group_scores_view
    from ..ops.groups import wave_fold

    gc = carry_l.groups
    S = wt.shape[0]
    n_local = na_l.cap.shape[0]
    offset = (lax.axis_index(NODE_AXIS) * n_local).astype(jnp.int32)
    garange = offset + jnp.arange(n_local, dtype=jnp.int32)
    fields = {name: getattr(table, name)[wt] for name in PodTableDev._fields}
    rows = PodRow(valid=jnp.ones((S,), bool),
                  sig=jnp.ones((S,), jnp.int32), **fields)
    static_mask, taint_raw, na_raw, s_img = statics_l

    def fit_one(pod: PodRow):
        fit_ok = fit_mask(na_l.cap, carry_l.used, carry_l.npods,
                          na_l.allowed_pods, pod.req)
        s_fit, s_bal = _fit_scores(cfg, na_l, carry_l, pod)
        return fit_ok, s_fit, s_bal

    fit0, sfit0, sbal0 = jax.vmap(fit_one)(rows)

    if has_groups:
        f_act = gd_l.spr_f_active[wt]
        f_skew = gd_l.spr_f_max_skew[wt]
        f_self = gd_l.spr_f_self[wt]
        f_minz = gc.spr_f_min_zero[wt]
        f_tv = gd_l.spr_f_tv[wt]
        f_elig = gd_l.spr_f_elig[wt]
        s_act = gd_l.spr_s_active[wt]
        s_skew = gd_l.spr_s_max_skew[wt]
        s_ishost = gd_l.spr_s_is_host[wt]
        s_tv = gd_l.spr_s_tv[wt]
        s_elig = gd_l.spr_s_elig[wt]
        s_keys = gd_l.spr_s_keys_ok[wt]
        s_dom = gd_l.spr_s_dom[wt]
        ra_act = gd_l.ipa_ra_active[wt]
        ra_tv = gd_l.ipa_ra_tv[wt]
        raa_act = gd_l.ipa_raa_active[wt]
        raa_tv = gd_l.ipa_raa_tv[wt]
        self_all = gd_l.ipa_self_all[wt]
        stc_tv = gd_l.ipa_stc_tv[wt]
        stp_tv = gd_l.ipa_stp_tv[wt]
        m_f = gd_l.m_spr_f[wt][:, wt]
        m_s = gd_l.m_spr_s[wt][:, wt]
        m_a = gd_l.m_ipa_a[wt][:, wt]
        m_aa = gd_l.m_ipa_aa[wt][:, wt]
        m_ex = gd_l.m_ipa_exist[wt][:, wt]
        w_c = gd_l.w_stc[wt][:, wt]
        w_p = gd_l.w_stp[wt][:, wt]

    st0 = _WaveState(
        used=carry_l.used, nonzero_used=carry_l.nonzero_used,
        npods=carry_l.npods,
        fit_ok=fit0, s_fit=sfit0, s_bal=sbal0,
        f_cnt=gc.spr_f_cnt[wt] if has_groups else None,
        s_cnt=gc.spr_s_cnt[wt] if has_groups else None,
        veto=gc.ipa_veto[wt] if has_groups else None,
        a_cnt=gc.ipa_a_cnt[wt] if has_groups else None,
        a_total=gc.ipa_a_total[wt] if has_groups else None,
        aa_cnt=gc.ipa_aa_cnt[wt] if has_groups else None,
        iscore=gc.ipa_score[wt] if has_groups else None,
        cnt_sn=jnp.zeros((S, n_local), jnp.int32) if has_groups else None,
        clean=jnp.bool_(True), n_conf=jnp.int32(0), prefix=jnp.int32(0),
        ports=carry_l.ports if has_ports else None)

    def own(v, in_shard):
        # the chosen node's value, broadcast from the owning shard
        z = jnp.where(in_shard, v, jnp.zeros_like(v))
        if z.dtype == jnp.bool_:
            return lax.psum(z.astype(jnp.int32), NODE_AXIS).astype(bool)
        return lax.psum(z, NODE_AXIS)

    def _eval(stx: _WaveState, w):
        feasible = static_mask[w] & stx.fit_ok[w]
        if has_ports:
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
                veto=stx.veto[w], a_cnt=stx.a_cnt[w],
                a_total=stx.a_total[w],
                aa_cnt=stx.aa_cnt[w], iscore=stx.iscore[w])
            feasible &= group_mask_view(view, fam, axis=NODE_AXIS)
        if norm_live:
            s_taint = default_normalize(taint_raw[w], feasible,
                                        reverse=True, axis=NODE_AXIS)
            s_na = default_normalize(na_raw[w], feasible, reverse=False,
                                     axis=NODE_AXIS)
            tn = cfg.w_taint * s_taint + cfg.w_node_affinity * s_na
        else:
            tn = cfg.w_taint * MAX_SCORE
        total = (cfg.w_fit * stx.s_fit[w] + cfg.w_balanced * stx.s_bal[w]
                 + tn + cfg.w_image * s_img[w])
        if has_groups:
            total = total + group_scores_view(cfg.w_spread, cfg.w_ipa, view,
                                              feasible, fam, axis=NODE_AXIS,
                                              n_global=n_global)
        return feasible, total

    def _argmax_global(masked):
        lbest = jnp.argmax(masked).astype(jnp.int32)
        lscore = masked[lbest]
        gscore = pmax(lscore, NODE_AXIS)
        cand = jnp.where(lscore == gscore, offset + lbest, _INT_MAX)
        return pmin(cand, NODE_AXIS), gscore

    def spec_one(s):
        feas, tot = _eval(st0, s)
        best, gscore = _argmax_global(jnp.where(feas, tot, -1))
        return jnp.where(gscore >= 0, best, jnp.int32(-1))

    spec_y = jax.vmap(spec_one)(jnp.arange(S, dtype=jnp.int32))

    cols = jnp.array(cfg.score_cols, jnp.int32)
    nzm = jnp.array(cfg.col_nonzero)
    slots = jnp.array(cfg.nonzero_slot, jnp.int32)

    def step(stx: _WaveState, x):
        w = x.widx
        feasible, total = _eval(stx, w)
        best, gscore = _argmax_global(jnp.where(feasible, total, -1))
        assigned = (gscore >= 0) & x.valid
        g_i = assigned.astype(jnp.int32)
        lid = best - offset
        in_shard = (lid >= 0) & (lid < n_local)
        lid_safe = jnp.clip(lid, 0, n_local - 1).astype(jnp.int32)
        onehot = (garange == best) & assigned
        req_w = rows.req[w]
        used = stx.used + jnp.where(onehot[:, None], req_w[None, :], 0)
        nzu = stx.nonzero_used + jnp.where(onehot[:, None],
                                           rows.nonzero_req[w][None, :], 0)
        npods = stx.npods + onehot.astype(stx.npods.dtype)

        gate_w = assigned & in_shard
        cap_row = own(na_l.cap[lid_safe], in_shard)
        used_row = own(used[lid_safe], in_shard)
        nz_row = own(nzu[lid_safe], in_shard)
        npods_b = own(npods[lid_safe], in_shard)
        allowed_b = own(na_l.allowed_pods[lid_safe], in_shard)

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
            return arr.at[:, lid_safe].set(jnp.where(gate_w, new,
                                                     arr[:, lid_safe]))

        fit_ok = put_col(stx.fit_ok, fit_b)
        s_fit = put_col(stx.s_fit, sfit_b)
        s_bal = put_col(stx.s_bal, sbal_b)

        f_cnt, s_cnt = stx.f_cnt, stx.s_cnt
        veto, a_cnt, a_total = stx.veto, stx.a_cnt, stx.a_total
        aa_cnt, iscore = stx.aa_cnt, stx.iscore
        if has_groups and fam.spr_f:
            tvb_f = own(f_tv[:, :, lid_safe], in_shard)       # [S, SC]
            eligb_f = own(f_elig[:, :, lid_safe], in_shard)
            inc_f = ((m_f[w] & eligb_f)[:, :, None]
                     & (f_tv == tvb_f[:, :, None])
                     & (tvb_f[:, :, None] != 0))
            f_cnt = stx.f_cnt + g_i * inc_f.astype(jnp.int32)
        if has_groups and fam.spr_s:
            tvb_s = own(s_tv[:, :, lid_safe], in_shard)
            eligb_s = own(s_elig[:, :, lid_safe], in_shard)
            is_b = ((garange == best) & assigned)[None, None, :]
            share_s = jnp.where(s_ishost[:, :, None], is_b,
                                (s_tv == tvb_s[:, :, None])
                                & (tvb_s[:, :, None] != 0))
            gate_c = jnp.where(s_ishost, m_s[w], m_s[w] & eligb_s)
            s_cnt = stx.s_cnt + g_i * (
                gate_c[:, :, None] & share_s).astype(jnp.int32)
        if has_groups and fam.ipa_anti:
            tvb_p_anti = own(raa_tv[w, :, lid_safe], in_shard)  # [TAA]
            share_anti = ((raa_tv[w] == tvb_p_anti[:, None])
                          & (tvb_p_anti[:, None] != 0))
            delta_veto = jnp.sum(m_ex[w][:, :, None] & share_anti[None],
                                 axis=1).astype(jnp.int32)
            veto = stx.veto + g_i * delta_veto
            tvb_aa = own(raa_tv[:, :, lid_safe], in_shard)
            share_aa = ((raa_tv == tvb_aa[:, :, None])
                        & (tvb_aa[:, :, None] != 0))
            inc_aa = m_aa[w][:, :, None] & share_aa
            aa_cnt = stx.aa_cnt + g_i * inc_aa.astype(jnp.int32)
        if has_groups and fam.ipa_req:
            tvb_a = own(ra_tv[:, :, lid_safe], in_shard)
            share_a = ((ra_tv == tvb_a[:, :, None])
                       & (tvb_a[:, :, None] != 0))
            inc_a = ((m_a[w][:, None] & ra_act)[:, :, None] & share_a)
            a_cnt = stx.a_cnt + g_i * inc_a.astype(jnp.int32)
            a_total = stx.a_total + (
                g_i * m_a[w]
                * jnp.sum(ra_act & (tvb_a != 0), axis=1)).astype(jnp.int64)
        if has_groups and fam.ipa_score:
            tvb_c = own(stc_tv[:, :, lid_safe], in_shard)
            share_c = ((stc_tv == tvb_c[:, :, None])
                       & (tvb_c[:, :, None] != 0))
            d_cons = _sum_terms(w_c[w][:, :, None] * share_c)
            tvb_p = own(stp_tv[w, :, lid_safe], in_shard)
            share_p = ((stp_tv[w] == tvb_p[:, None])
                       & (tvb_p[:, None] != 0))
            d_plcd = _sum_terms(w_p[w][:, :, None] * share_p[None])
            iscore = stx.iscore + assigned.astype(jnp.int64) * (
                d_cons + d_plcd)

        cnt_sn = (stx.cnt_sn.at[w, lid_safe].add(
            jnp.where(in_shard, g_i, 0)) if has_groups else None)
        ports2 = stx.ports
        if has_ports:
            prow = stx.ports[lid_safe]
            free = prow == 0
            rank = jnp.cumsum(free) - 1
            pp = rows.port_ids[w]
            nport = pp.shape[0]
            incoming = jnp.where((rank >= 0) & (rank < nport) & free,
                                 pp[jnp.clip(rank, 0, nport - 1)], 0)
            new_prow = jnp.where(free, incoming, prow)
            ports2 = stx.ports.at[lid_safe].set(
                jnp.where(gate_w & jnp.any(pp != 0), new_prow, prow))
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

    new_gc = (wave_fold(gd_l, gc, wt, stf.cnt_sn, fam=fam, axis=NODE_AXIS,
                        n_seg=n_global) if has_groups else carry_l.groups)
    new_carry = Carry(used=stf.used, nonzero_used=stf.nonzero_used,
                      npods=stf.npods,
                      ports=stf.ports if has_ports else carry_l.ports,
                      cache=carry_l.cache._replace(sig=jnp.int32(0)),
                      groups=new_gc)
    packed = jnp.concatenate(
        [ys, jnp.stack([stf.n_conf, stf.prefix])]).astype(jnp.int32)
    return new_carry, packed


@functools.partial(jax.jit, static_argnames=("cfg", "mesh", "fam",
                                             "norm_live", "has_groups",
                                             "has_ports"))
def _run_plan_sharded_jit(cfg: ScoreConfig, mesh: Mesh, na: NodeArrays,
                          carry: Carry, xs, table: PodTableDev, wt, gd,
                          statics, fam, norm_live: bool, has_groups: bool,
                          has_ports: bool):
    n_global = na.cap.shape[0]
    node_na = NodeArrays(*(P(NODE_AXIS) for _ in na))
    carry_spec = _carry_spec(carry)
    xs_spec = type(xs)(*(P() for _ in xs._fields))
    table_spec = PodTableDev(*(P() for _ in table))
    gd_spec = (_last_axis_spec(gd, _GD_NODE_FIELDS)
               if gd is not None else None)
    statics_spec = tuple(P(None, NODE_AXIS)
                         for _ in range(len(statics)))

    def local(na_l, carry_l, xs_r, table_r, wt_r, gd_l, statics_l):
        return _plan_local(cfg, n_global, fam, norm_live, has_groups,
                           has_ports, na_l, carry_l, xs_r, table_r, wt_r,
                           gd_l, statics_l)

    fn = _shard_map(local, mesh,
                    in_specs=(node_na, carry_spec, xs_spec, table_spec,
                              P(), gd_spec, statics_spec),
                    out_specs=(carry_spec, P()))
    return fn(na, carry, xs, table, wt, gd, statics)


@device_entry(cost=(48.0, 4.0))
def run_plan_sharded(cfg: ScoreConfig, mesh: Mesh, na: NodeArrays,
                     carry: Carry, xs, table: PodTableDev, wt,
                     gd: GroupsDev | None, statics, fam, norm_live: bool,
                     has_groups: bool = True, has_ports: bool = False):
    """`ops.program.run_plan` on the mesh: one compiled dispatch per
    mixed-signature span with the group counters as psum/all-reduces
    over the node axis. Serial-order exact (same conflict detection and
    repair as the single-device plan program); never donates — the mesh
    carry stays resident across the drain. `statics` are the
    SurfaceCache's [S, N] stacks, node axis sharded P(None, nodes)."""
    return _run_plan_sharded_jit, (cfg, mesh, na, carry, xs, table, wt, gd,
                                   statics, fam, norm_live, has_groups,
                                   has_ports)


# ---------------------------------------------------------------------------
# gang placement on the mesh


def _gang_scan_local(cfg: ScoreConfig, n_global: int, w_contig: int,
                     na_l: NodeArrays, carry_l: Carry, xs,
                     table: PodTableDev, wt, needed, dom_l, statics_l):
    """SPMD body of the sharded gang scan tier — `ops.gang.
    _run_gang_scan_impl` with the node axis local. The contiguity domain
    counts are replicated [n_global] (dense global domain ids); the
    all-or-nothing verdict is a replicated scalar, so the reject unwind
    leaves every shard's carry untouched."""
    n_local = na_l.cap.shape[0]
    offset = (lax.axis_index(NODE_AXIS) * n_local).astype(jnp.int32)
    garange = offset + jnp.arange(n_local, dtype=jnp.int32)
    cols = jnp.array(cfg.score_cols, jnp.int32)
    nzmask = jnp.array(cfg.col_nonzero)
    slots = jnp.array(cfg.nonzero_slot, jnp.int32)
    static_m, taint_raw, na_raw, s_img = statics_l            # [S, n_local]

    def _fit_parts(u):
        pod = _gather_row(table, PodXs(valid=jnp.bool_(True),
                                       sig=jnp.int32(0), tidx=u))
        fit_ok = fit_mask(na_l.cap, carry_l.used, carry_l.npods,
                          na_l.allowed_pods, pod.req)
        s_fit, s_bal = _fit_scores(cfg, na_l, carry_l, pod)
        return fit_ok, s_fit, s_bal

    fit_ok0, s_fit0, s_bal0 = jax.vmap(_fit_parts)(wt)
    req_s = table.req[wt]
    nzreq_s = table.nonzero_req[wt]
    skipb_s = table.skip_balanced[wt]

    def own(v, in_shard):
        z = jnp.where(in_shard, v, jnp.zeros_like(v))
        if z.dtype == jnp.bool_:
            return lax.psum(z.astype(jnp.int32), NODE_AXIS).astype(bool)
        return lax.psum(z, NODE_AXIS)

    def step(state, x):
        used, nz, npods, fit_ok, s_fit, s_bal, domcnt, placed = state
        s = x.widx
        pod = _gather_row(table, PodXs(valid=x.valid, sig=jnp.int32(0),
                                       tidx=x.tidx))
        feasible = static_m[s] & fit_ok[s]
        s_taint = default_normalize(taint_raw[s], feasible, reverse=True,
                                    axis=NODE_AXIS)
        s_na = default_normalize(na_raw[s], feasible, reverse=False,
                                 axis=NODE_AXIS)
        total = (cfg.w_fit * s_fit[s] + cfg.w_balanced * s_bal[s]
                 + cfg.w_taint * s_taint + cfg.w_node_affinity * s_na
                 + cfg.w_image * s_img[s])
        if w_contig:
            total = total + w_contig * default_normalize(
                domcnt[dom_l].astype(jnp.int64), feasible, reverse=False,
                axis=NODE_AXIS)
        masked = jnp.where(feasible, total, jnp.int64(-1))
        lbest = jnp.argmax(masked).astype(jnp.int32)
        lscore = masked[lbest]
        gscore = pmax(lscore, NODE_AXIS)
        cand = jnp.where(lscore == gscore, offset + lbest, _INT_MAX)
        best = pmin(cand, NODE_AXIS)
        assigned = (gscore >= 0) & x.valid
        lid = best - offset
        in_shard = (lid >= 0) & (lid < n_local)
        lid_safe = jnp.clip(lid, 0, n_local - 1).astype(jnp.int32)
        onehot = (garange == best) & assigned
        used2 = used + jnp.where(onehot[:, None], pod.req[None, :], 0)
        nz2 = nz + jnp.where(onehot[:, None], pod.nonzero_req[None, :], 0)
        npods2 = npods + onehot.astype(npods.dtype)

        cap_row = own(na_l.cap[lid_safe], in_shard)
        used_row = own(used2[lid_safe], in_shard)
        npods_row = own(npods2[lid_safe], in_shard)
        nz_row = own(nz2[lid_safe], in_shard)
        allowed_b = own(na_l.allowed_pods[lid_safe], in_shard)

        def _refresh(req, nzreq, skipb):
            fit_b = ((npods_row + 1 <= allowed_b)
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
        wr = assigned & in_shard
        fit_ok2 = fit_ok.at[:, lid_safe].set(
            jnp.where(wr, fo_b, fit_ok[:, lid_safe]))
        s_fit2 = s_fit.at[:, lid_safe].set(
            jnp.where(wr, sf_b, s_fit[:, lid_safe]))
        s_bal2 = s_bal.at[:, lid_safe].set(
            jnp.where(wr, sb_b, s_bal[:, lid_safe]))
        if w_contig:
            dom_b = own(dom_l[lid_safe], in_shard)
            domcnt2 = domcnt.at[dom_b].add(
                jnp.where(assigned, 1, 0).astype(domcnt.dtype))
        else:
            domcnt2 = domcnt
        placed2 = placed + assigned.astype(placed.dtype)
        return ((used2, nz2, npods2, fit_ok2, s_fit2, s_bal2, domcnt2,
                 placed2), jnp.where(assigned, best, jnp.int32(-1)))

    state0 = (carry_l.used, carry_l.nonzero_used, carry_l.npods,
              fit_ok0, s_fit0, s_bal0,
              jnp.zeros((n_global,), jnp.int32), jnp.int32(0))
    (used_f, nz_f, npods_f, _, _, _, _, placed), raw = lax.scan(
        step, state0, xs)
    accept = placed >= needed

    def sel(a, b):
        return jnp.where(accept, a, b)

    cache = carry_l.cache._replace(
        sig=jnp.where(accept, jnp.int32(0), carry_l.cache.sig))
    carry_out = carry_l._replace(used=sel(used_f, carry_l.used),
                                 nonzero_used=sel(nz_f,
                                                  carry_l.nonzero_used),
                                 npods=sel(npods_f, carry_l.npods),
                                 cache=cache)
    packed = jnp.concatenate([
        raw, jnp.stack([accept.astype(jnp.int32), placed,
                        jnp.int32(1), jnp.int32(1)])])
    return carry_out, packed


@functools.partial(jax.jit, static_argnames=("cfg", "mesh", "w_contig"))
def _run_gang_scan_sharded_jit(cfg: ScoreConfig, mesh: Mesh, na: NodeArrays,
                               carry: Carry, xs, table: PodTableDev, wt,
                               needed, dom, statics, w_contig: int):
    n_global = na.cap.shape[0]
    node_na = NodeArrays(*(P(NODE_AXIS) for _ in na))
    carry_spec = _carry_spec(carry)
    xs_spec = type(xs)(*(P() for _ in xs._fields))
    table_spec = PodTableDev(*(P() for _ in table))
    statics_spec = tuple(P(None, NODE_AXIS)
                         for _ in range(len(statics)))

    def local(na_l, carry_l, xs_r, table_r, wt_r, need_r, dom_l, statics_l):
        return _gang_scan_local(cfg, n_global, w_contig, na_l, carry_l,
                                xs_r, table_r, wt_r, need_r, dom_l,
                                statics_l)

    fn = _shard_map(local, mesh,
                    in_specs=(node_na, carry_spec, xs_spec, table_spec,
                              P(), P(), P(NODE_AXIS), statics_spec),
                    out_specs=(carry_spec, P()))
    return fn(na, carry, xs, table, wt, needed, dom, statics)


@functools.partial(jax.jit, static_argnames=("cfg", "mesh", "L", "K", "J"))
def _run_gang_uniform_sharded_jit(cfg: ScoreConfig, mesh: Mesh,
                                  na: NodeArrays, carry: Carry, x: PodXs,
                                  table: PodTableDev, n_actual, needed,
                                  L: int, K: int, J: int):
    n_global = na.cap.shape[0]
    node_na = NodeArrays(*(P(NODE_AXIS) for _ in na))
    carry_spec = _carry_spec(carry)
    x_spec = PodXs(*(P() if v is not None else None for v in x))
    table_spec = PodTableDev(*(P() for _ in table))

    def local(na_l, carry_l, x_r, table_r, n_act, need):
        new_carry, assignments, ok, depth_ok = _uniform_local_core(
            cfg, n_global, L, K, J, na_l, carry_l, x_r, table_r, n_act)
        placed = jnp.sum((assignments >= 0).astype(jnp.int32))
        accept = placed >= need
        apply = accept & ok & depth_ok
        carry_out = jax.tree_util.tree_map(
            lambda a, b: jnp.where(apply, a, b), new_carry, carry_l)
        return carry_out, assignments, accept, placed, ok, depth_ok

    fn = _shard_map(local, mesh,
                    in_specs=(node_na, carry_spec, x_spec, table_spec,
                              P(), P()),
                    out_specs=(carry_spec, P(), P(), P(), P(), P()))
    carry_out, assignments, accept, placed, ok, depth_ok = fn(
        na, carry, x, table, n_actual, needed)
    packed = jnp.concatenate([
        assignments,
        jnp.stack([accept, placed, ok, depth_ok]).astype(jnp.int32)])
    return carry_out, packed


@device_entry(cost=(64.0, 5.0))
def run_gang_sharded(cfg: ScoreConfig, mesh: Mesh, na: NodeArrays,
                     carry: Carry, xs, table: PodTableDev, wt=None,
                     needed=None, dom=None, statics=None, w_contig: int = 0,
                     uniform: bool = False, n_actual=None, L: int = 0,
                     K: int = 0, J: int = 0):
    """`ops.gang.run_gang` on the mesh — both tiers behind one entry,
    packed layouts identical to the single-device kernel's. Never
    donates: the scheduler keeps the input carry to replay failed
    uniform-tier preconditions on the scan tier, and the reject unwind
    is on-device on every shard."""
    if uniform:
        return _run_gang_uniform_sharded_jit, (cfg, mesh, na, carry, xs,
                                               table, n_actual, needed, L,
                                               K, J)
    return _run_gang_scan_sharded_jit, (cfg, mesh, na, carry, xs, table, wt,
                                        needed, dom, statics, w_contig)


# ---------------------------------------------------------------------------
# dirty-row upload onto the resident mesh copy (the PR-9 columnar-ingest
# win carried over: mesh drains stop paying full-matrix re-uploads)


@functools.partial(jax.jit, static_argnames=("mesh",))
def _scatter_rows_sharded_jit(mesh: Mesh, dev: NodeArrays, idx,
                              rows: NodeArrays) -> NodeArrays:
    def local(dev_l, idx_r, rows_r):
        n_local = dev_l.cap.shape[0]
        offset = (lax.axis_index(NODE_AXIS) * n_local).astype(jnp.int32)
        lid = idx_r - offset
        m = (lid >= 0) & (lid < n_local)
        # out-of-shard rows route to index n_local and DROP: clipping
        # them in-range would collide a masked duplicate with a real
        # in-shard write at the boundary rows, and XLA scatter picks an
        # arbitrary winner among duplicate indices — the real update can
        # silently lose. (Pad duplicates carry identical values, so they
        # stay order-independent.)
        tgt = jnp.where(m, lid, n_local).astype(jnp.int32)

        def one(d, r):
            return d.at[tgt].set(r.astype(d.dtype), mode="drop")

        return NodeArrays(*(one(d, r) for d, r in zip(dev_l, rows_r)))

    fn = _shard_map(local, mesh,
                    in_specs=(NodeArrays(*(P(NODE_AXIS) for _ in dev)),
                              P(), NodeArrays(*(P() for _ in rows))),
                    out_specs=NodeArrays(*(P(NODE_AXIS) for _ in dev)))
    return fn(dev, idx, rows)


@device_entry(cost=(2.0, 3.0))
def scatter_rows_sharded(mesh: Mesh, dev: NodeArrays, idx,
                         rows: NodeArrays) -> NodeArrays:
    """Scatter `rows` (replicated, [B, ...] per leaf) into the resident
    node-sharded arrays at global row ids `idx` (i32 [B], pow2-padded by
    repeating a real index — duplicate writes carry identical values).
    Each shard keeps only its own rows; the H2D bytes are the small
    replicated row block, not the full matrix."""
    _note_shard_upload("host_snapshot", rows)
    return _scatter_rows_sharded_jit, (mesh, dev, idx, rows)


# ---------------------------------------------------------------------------
# on-device cluster analytics on the mesh: one all-gather, then the exact
# single-device probe reduction on the reassembled arrays


@functools.partial(jax.jit, static_argnames=("mesh", "ndom"))
def _cluster_probe_sharded_jit(mesh: Mesh, na: NodeArrays, carry: Carry,
                               dom, ndom: int):
    from ..ops.program import _probe_math

    def local(cap, valid, used, npods, dom_r):
        g = functools.partial(lax.all_gather, axis_name=NODE_AXIS,
                              axis=0, tiled=True)
        cap_g, valid_g, used_g, npods_g = g(cap), g(valid), g(used), \
            g(npods)
        R = cap.shape[1]

        # lane 0 runs the reduction on the gathered arrays; the other
        # lanes skip it (the sort/percentile pass is the probe's whole
        # cost — running it replicated on every lane multiplies the
        # drain's probe bill by the mesh size for identical answers)
        def compute(_):
            return _probe_math(cap_g, valid_g, used_g, npods_g, dom_r,
                               ndom)

        def skip(_):
            return (jnp.zeros((R, 7), jnp.float32),
                    jnp.zeros((4,), jnp.float32), jnp.int32(0))

        out = lax.cond(lax.axis_index(NODE_AXIS) == 0, compute, skip,
                       None)
        # broadcast lane 0's result by gathering and slicing — exact
        # (no cross-lane arithmetic that could perturb a float bit)
        return jax.tree_util.tree_map(
            lambda x: lax.all_gather(x, NODE_AXIS, axis=0)[0], out)

    # one tiled all-gather per column, then single-lane compute: feeding
    # the sharded carry straight into the single-device probe jit makes
    # GSPMD reshard around the cross-node sort/percentile ops instead —
    # an order of magnitude slower per drain on the host mesh
    fn = _shard_map(local, mesh,
                    in_specs=(P(NODE_AXIS), P(NODE_AXIS), P(NODE_AXIS),
                              P(NODE_AXIS), P()),
                    out_specs=(P(), P(), P()))
    return fn(na.cap, na.valid, carry.used, carry.npods, dom)


@device_entry(cost=(24.0, 3.0))
def cluster_probe_sharded(mesh: Mesh, na: NodeArrays, carry: Carry, dom,
                          ndom: int):
    """`ops.program.cluster_probe`'s mesh twin: all-gathers the node
    shards inside one sharded program and runs the identical `_probe_math`
    reduction on the reassembled arrays, so every output element is
    bit-identical to the single-device probe (tests/test_cluster_probe.py
    oracle transitively holds). `dom` is replicated; the carry and node
    arrays stay resident shards — zero extra h2d, like the original."""
    return _cluster_probe_sharded_jit, (mesh, na, carry, dom, ndom)
