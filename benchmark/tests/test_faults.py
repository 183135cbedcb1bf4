"""`correct` comes out false when the timed path is broken underneath: the
harness's look for a chip is skipped and the rest of a run is driven, once
per fault the cells can have. (The exchange between chips is not among
them: every cell runs on one chip.)"""

import time

import pytest

import kubernetes_tpu.scheduler as scheduler_mod
from benchmark.harness import run_cell
from benchmark.tests.small import small_root


def _state_unchanged(monkeypatch):
    """The uniform step hands back the carry it was given."""
    real = scheduler_mod.run_uniform

    def stale(cfg, na, carry, *a, **kw):
        _new, packed = real(cfg, na, carry, *a, **kw)
        return carry, packed
    monkeypatch.setattr(scheduler_mod, "run_uniform", stale)


def _half_batch(monkeypatch):
    """Each drain takes its pods and schedules only the first half."""
    from kubernetes_tpu.backend.queue import SchedulingQueue
    real = SchedulingQueue.drain

    def half(self, max_pods=0):
        out = real(self, max_pods)
        return out[:(len(out) + 1) // 2]
    monkeypatch.setattr(SchedulingQueue, "drain", half)


def _answer_altered(monkeypatch):
    """Every 7th bind names the next node instead of the decided one."""
    from kubernetes_tpu.backend.apiserver import APIServer
    real = APIServer.bind_all
    count = [0]

    def altered(self, pairs, fence_token=None):
        out = []
        for pod, original in pairs:
            count[0] += 1
            if count[0] % 7 == 0:
                i = int(pod.spec.node_name.rsplit("-", 1)[1])
                pod = pod.with_node_name(
                    f"node-{(i + 1) % len(self.nodes)}")
            out.append((pod, original))
        return real(self, out, fence_token)
    monkeypatch.setattr(APIServer, "bind_all", altered)


@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch,
                                   _answer_altered])
def test_fault_is_not_correct(tmp_path, monkeypatch, fault):
    root = small_root(tmp_path)
    fault(monkeypatch)
    r = run_cell(root, "Small-Basic.small-closed", 2 ** 31 + 23, 2.0, False,
                 time.perf_counter(), require_tpu=False)
    assert not r["correct"], r["checks"]
