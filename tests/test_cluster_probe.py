"""`cluster_probe` (ISSUE 13): the thirteenth kernel's bit-parity gate.

The probe's contract (ops/program.py) is bit-reproducibility: every
cross-node reduction is exact int64 arithmetic, floats appear only in
elementwise division/compare, sort and gather — all deterministic
between XLA and numpy. This file holds that contract with a full numpy
oracle at 5k nodes (EXACT equality, not allclose), pins the edge cases
(empty cluster, absent resource, saturated cluster, single domain), and
proves the kernel's rails discipline: warm re-calls fit a zero retrace
budget and the whole probe runs under `jax.transfer_guard("disallow")`
on pre-staged device inputs — zero h2d beyond the resident carry.
"""

import jax
import jax.numpy as jnp
import numpy as np

from kubernetes_tpu.analysis.rails import GLOBAL as RAILS
from kubernetes_tpu.ops.program import (PROBE_STATS, PROBE_TIGHT, Carry,
                                        _PROBE_QS, cluster_probe,
                                        initial_carry)
from kubernetes_tpu.state.tensorize import NodeArrays


def _device_state(cap, used, valid, npods):
    """A minimal NodeArrays + carry pair: the probe only reads cap /
    valid / carry.used / carry.npods; every other column is a stub."""
    n = cap.shape[0]
    z32 = jnp.zeros((n, 1), jnp.int32)
    na = NodeArrays(
        cap=jnp.asarray(cap, jnp.int64),
        used=jnp.asarray(used, jnp.int64),
        nonzero_used=jnp.zeros((n, 2), jnp.int64),
        npods=jnp.asarray(npods, jnp.int32),
        allowed_pods=jnp.full((n,), 110, jnp.int32),
        valid=jnp.asarray(valid, bool),
        unschedulable=jnp.zeros((n,), bool),
        name_id=jnp.zeros((n,), jnp.int32),
        taint_key=z32, taint_val=z32, taint_eff=z32,
        label_key=z32, label_kv=z32,
        label_num=jnp.zeros((n, 1), jnp.int64),
        ports=z32, image_id=z32,
        image_size=jnp.zeros((n, 1), jnp.int64),
    )
    return na, initial_carry(na)


def _oracle(cap, used, valid, npods, dom, ndom):
    """The numpy twin of _cluster_probe_jit — same dtypes, same op
    order, so every output element must match bit-for-bit."""
    f32 = np.float32
    cap = np.asarray(cap, np.int64)
    used = np.asarray(used, np.int64)
    valid = np.asarray(valid, bool)
    part = valid[:, None] & (cap > 0)
    used_m = np.where(part, used, 0).astype(np.int64)
    cap_m = np.where(part, cap, 0).astype(np.int64)
    util = np.where(part,
                    used_m.astype(f32) / np.maximum(cap_m, 1).astype(f32),
                    f32(-1.0)).astype(f32)
    m = part.sum(axis=0).astype(np.int32)
    n_total, n_res = util.shape

    srt = np.sort(util, axis=0)
    mf = m.astype(np.float64)
    cols = []
    for q in _PROBE_QS + (1.0,):
        idx = np.floor(q * (mf - 1.0) + 0.5).astype(np.int32)
        at = np.clip(n_total - m + idx, 0, n_total - 1)
        col = srt[at, np.arange(n_res)]
        cols.append(np.where(m > 0, col, f32(0.0)).astype(f32))

    sum_used = used_m.sum(axis=0, dtype=np.int64)
    sum_cap = cap_m.sum(axis=0, dtype=np.int64)
    mean = np.where(sum_cap > 0,
                    sum_used.astype(f32) / np.maximum(sum_cap, 1).astype(f32),
                    f32(0.0)).astype(f32)

    free = cap_m - used_m
    tot_free = free.sum(axis=0, dtype=np.int64)
    max_free = free.max(axis=0)
    frag = np.where(tot_free > 0,
                    f32(1.0) - max_free.astype(f32)
                    / np.maximum(tot_free, 1).astype(f32),
                    f32(0.0)).astype(f32)

    bottleneck = np.max(np.where(part, util, f32(0.0)), axis=1)
    tight = valid & (bottleneck >= f32(PROBE_TIGHT))
    stranded_free = np.where(tight[:, None], free, 0).sum(axis=0,
                                                          dtype=np.int64)
    stranded = np.where(tot_free > 0,
                        stranded_free.astype(f32)
                        / np.maximum(tot_free, 1).astype(f32),
                        f32(0.0)).astype(f32)

    per_res = np.stack(cols + [mean, frag, stranded], axis=1).astype(f32)

    dclip = np.clip(np.asarray(dom, np.int32), 0, ndom - 1)
    dom_pods = np.zeros((ndom,), np.int64)
    np.add.at(dom_pods, dclip, np.where(valid, npods, 0).astype(np.int64))
    dom_nodes = np.zeros((ndom,), np.int64)
    np.add.at(dom_nodes, dclip, valid.astype(np.int64))
    has = dom_nodes > 0
    load = np.where(has,
                    dom_pods.astype(f32) / np.maximum(dom_nodes, 1).astype(f32),
                    f32(0.0))
    if has.any():
        dmax, dmin = load[has].max(), load[has].min()
        dom_stats = np.array([has.sum(), dmax, dmin, dmax - dmin], f32)
    else:
        dom_stats = np.zeros((4,), f32)
    return per_res, dom_stats, np.int32(valid.sum())


def _random_cluster(rng, n, r, ndom):
    """Adversarial mix: zero-capacity cells, a resource nobody
    advertises, invalid nodes, a band of saturated (tight) nodes."""
    cap = rng.integers(0, 200, size=(n, r), dtype=np.int64)
    cap[:, r - 1] = 0                       # resource with m == 0
    cap[rng.random(n) < 0.1] = 0            # nodes advertising nothing
    frac = rng.random((n, r))
    used = np.minimum((cap * frac).astype(np.int64), cap)
    tight_rows = rng.random(n) < 0.15       # saturate the bottleneck
    used[tight_rows, 0] = cap[tight_rows, 0]
    valid = rng.random(n) < 0.9
    npods = rng.integers(0, 50, size=(n,), dtype=np.int32)
    dom = rng.integers(0, ndom, size=(n,), dtype=np.int32)
    return cap, used, valid, npods, dom


def _assert_probe_matches(cap, used, valid, npods, dom, ndom):
    na, carry = _device_state(cap, used, valid, npods)
    per_res, dom_stats, count = cluster_probe(
        na, carry, jnp.asarray(dom, jnp.int32), ndom)
    o_per, o_dom, o_count = _oracle(cap, used, valid, npods, dom, ndom)
    got_per = np.asarray(per_res)
    got_dom = np.asarray(dom_stats)
    assert got_per.dtype == np.float32 and got_per.shape == (cap.shape[1], 7)
    assert np.array_equal(got_per, o_per), (
        f"per-res divergence:\nxla={got_per}\noracle={o_per}")
    assert np.array_equal(got_dom, o_dom)
    assert int(count) == int(o_count)
    return got_per


class TestClusterProbeParity:
    def test_bit_parity_vs_numpy_oracle_5k_nodes(self):
        rng = np.random.default_rng(13)
        cap, used, valid, npods, dom = _random_cluster(rng, 5000, 16, 9)
        per = _assert_probe_matches(cap, used, valid, npods, dom, 9)
        # the adversarial mix must actually exercise every stat column
        stats = dict(zip(PROBE_STATS, per.T))
        assert stats["max"].max() > 0 and stats["mean"].max() > 0
        assert stats["frag"].max() > 0 and stats["stranded"].max() > 0

    def test_bit_parity_fuzz_small_shapes(self):
        for seed in range(5):
            rng = np.random.default_rng(100 + seed)
            n = int(rng.integers(1, 64))
            ndom = int(rng.integers(1, 5))
            cap, used, valid, npods, dom = _random_cluster(rng, n, 6, ndom)
            _assert_probe_matches(cap, used, valid, npods, dom, ndom)

    def test_empty_cluster_all_invalid(self):
        n, r = 16, 4
        cap = np.full((n, r), 10, np.int64)
        used = np.zeros((n, r), np.int64)
        valid = np.zeros((n,), bool)
        per = _assert_probe_matches(cap, used, valid,
                                    np.zeros((n,), np.int32),
                                    np.zeros((n,), np.int32), 1)
        assert not per.any()

    def test_saturated_cluster_stranded_is_total(self):
        """Every node tight with free memory left: ALL free capacity is
        stranded, fragmentation matches the oracle, p50==p90==p99."""
        n = 32
        cap = np.tile(np.array([[100, 400]], np.int64), (n, 1))
        used = np.tile(np.array([[100, 100]], np.int64), (n, 1))
        valid = np.ones((n,), bool)
        per = _assert_probe_matches(cap, used, valid,
                                    np.full((n,), 5, np.int32),
                                    np.zeros((n,), np.int32), 1)
        stats = dict(zip(PROBE_STATS, per.T))
        assert stats["stranded"][1] == np.float32(1.0)
        assert stats["p50"][0] == stats["p99"][0] == np.float32(1.0)


class TestClusterProbeRails:
    def test_warm_recall_fits_zero_retrace_budget(self):
        """Same shapes + same static ndom ⇒ no fresh XLA compile — the
        per-drain sampling loop never pays a retrace inside rails
        windows after warm-up."""
        rng = np.random.default_rng(5)
        cap, used, valid, npods, dom = _random_cluster(rng, 256, 8, 3)
        na, carry = _device_state(cap, used, valid, npods)
        dom_dev = jnp.asarray(dom, jnp.int32)
        cluster_probe(na, carry, dom_dev, 3)[0].block_until_ready()  # warm
        RAILS.enable(True)
        try:
            with RAILS.retrace_budget(0, kernels=("cluster_probe",)):
                cap2, used2, valid2, npods2, dom2 = _random_cluster(
                    np.random.default_rng(6), 256, 8, 3)
                na2, carry2 = _device_state(cap2, used2, valid2, npods2)
                out = cluster_probe(na2, carry2,
                                    jnp.asarray(dom2, jnp.int32), 3)
                out[0].block_until_ready()
        finally:
            RAILS.enable(False)

    def test_probe_runs_under_transfer_guard_disallow(self):
        """Pre-staged device inputs: the probe itself moves zero bytes
        host↔device (the 'zero extra h2d' acceptance line)."""
        rng = np.random.default_rng(11)
        cap, used, valid, npods, dom = _random_cluster(rng, 128, 8, 4)
        na, carry = _device_state(cap, used, valid, npods)
        dom_dev = jnp.asarray(dom, jnp.int32)
        cluster_probe(na, carry, dom_dev, 4)[0].block_until_ready()  # warm
        with jax.transfer_guard("disallow"):
            per_res, dom_stats, count = cluster_probe(na, carry, dom_dev, 4)
            per_res.block_until_ready()
        o_per, o_dom, o_count = _oracle(cap, used, valid, npods, dom, 4)
        assert np.array_equal(np.asarray(per_res), o_per)
        assert np.array_equal(np.asarray(dom_stats), o_dom)
        assert int(count) == int(o_count)


class TestProbeRegistration:
    def test_all_kernels_ledgered_and_sanitized(self):
        from kubernetes_tpu.perf.ledger import device_entries
        kernels = device_entries()
        assert "cluster_probe" in kernels and len(kernels) == 19
        assert "cluster_probe_sharded" in kernels
        # the declaration is the dispatch path: staged and measured
        assert kernels["cluster_probe"].fn is cluster_probe
