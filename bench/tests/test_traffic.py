"""Inputs from the seed, and the window's closed loop on a service
stand-in."""
import dataclasses

import numpy as np
import pytest

from bench import data, run, window

MIX = [{"weight": 1, "terms": 1, "attrs_per_term": 2, "width": 0.3},
       {"weight": 1, "terms": 4, "attrs_per_term": 1, "width": 0.05, "distinct_attrs": True}]


def test_same_seed_same_inputs_any_size_of_seed():
    corpus = {"rows": 500, "dim": 16, "attrs": 4, "modes": 8, "intrinsic": 4}
    a = data.make_corpus(2**33 + 5, corpus, 10)
    b = data.make_corpus(2**33 + 5, corpus, 10)
    c = data.make_corpus(5, corpus, 10)
    for u, v, w in zip(a, b, c):
        np.testing.assert_array_equal(u, v)
        assert not np.array_equal(u, w)
    assert a[0].shape == (500, 16) and a[1].shape == (500, 4) and a[2].shape == (10, 16)
    p = data.make_predicates(data.host_rng(2**33 + 5, 1), 64, MIX, 4)
    q = data.make_predicates(data.host_rng(2**33 + 5, 1), 64, MIX, 4)
    for i in range(64):
        np.testing.assert_array_equal(p[i][0], q[i][0])


def test_every_seed_sends_the_same_sequence_of_shapes():
    assert list(data.shape_sequence([0.5, 0.5], 6)) == [0, 1, 0, 1, 0, 1]
    assert list(data.shape_sequence([3, 1], 8)) == [0, 0, 1, 0, 0, 0, 1, 0]
    a = data.make_predicates(data.host_rng(1, 1), 100, MIX, 4)
    b = data.make_predicates(data.host_rng(2, 1), 100, MIX, 4)
    np.testing.assert_array_equal(a.kind, b.kind)
    assert not np.array_equal(a[0][0], b[0][0])


def test_predicate_shapes_pass_the_stated_shares():
    rng = np.random.default_rng(1)
    attrs = rng.uniform(size=(20000, 4)).astype(np.float32)
    preds = data.make_predicates(rng, 400, MIX, 4)
    share = {1: [], 4: []}
    for i in range(len(preds)):
        lo, hi = preds[i]
        ok = np.any(np.all((attrs[:, None] >= lo) & (attrs[:, None] <= hi), -1), -1)
        share[lo.shape[0]].append(ok.mean())
        assert (lo <= hi).all()
        constrained = (lo > data.F32_MIN).sum(axis=1)
        assert list(constrained) == ([2] if lo.shape[0] == 1 else [1, 1, 1, 1])
    assert np.mean(share[1]) == pytest.approx(0.09, abs=0.01)
    assert np.mean(share[4]) == pytest.approx(1 - 0.95**4, abs=0.01)


@dataclasses.dataclass
class _Ans:
    rid: int
    ids: np.ndarray
    dists: np.ndarray


class FakeService:
    """Micro-batches of ``batch``, one queue per predicate term count; each
    ``step`` takes ``exec_s`` and serves the fuller queue, or with ``starve``
    never the queue of that many terms."""

    def __init__(self, batch, exec_s, clock, starve=None):
        self.batch, self.exec_s, self.clock, self.starve = batch, exec_s, clock, starve
        self.queues, self.rid = {}, 0

    def submit(self, name, query, pred):
        self.rid += 1
        self.queues.setdefault(pred.lo.shape[0], []).append(self.rid)
        return self.rid

    def pending(self):
        return sum(len(q) for q in self.queues.values())

    def step(self):
        self.clock.t += self.exec_s
        ready = [t for t, q in self.queues.items() if q and t != self.starve]
        if not ready:
            return []
        t = max(ready, key=lambda t: (len(self.queues[t]), -t))
        jobs, self.queues[t] = self.queues[t][: self.batch], self.queues[t][self.batch :]
        return [_Ans(r, np.zeros(1), np.zeros(1)) for r in jobs]


class Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def _preds(n):
    return data.make_predicates(np.random.default_rng(0), n, MIX, 4)


CLOSED = {"arrivals": {"kind": "closed", "clients": 8}}


def test_closed_loop_window_is_batch_aligned():
    clock = Clock()
    svc = FakeService(batch=4, exec_s=1.0, clock=clock)
    w = window.drive(svc, "c", np.zeros((64, 2), np.float32), _preds(64), CLOSED, 5.5,
                     clock=clock)
    # 8 callers, 4 per batch of 1 s, shapes alternating: the window closes at
    # the 6th delivery, and the batches alternate between the two queues
    assert w.batches == 6 and w.seconds == 6.0 and len(w.answers) == 24
    assert w.step_shape == [0, 1, 0, 1, 0, 1]
    assert w.queued_at_close == 4 and w.lost == 0
    assert sorted(set(w.latencies_s)) == [1.0, 2.0, 3.0]
    assert w.open_ages_s == [1.0] * 4  # submitted at the 5th delivery
    assert w.step_s == [1.0] * 6
    # the pool in order, conjunctions at even places, disjunctions at odd
    assert [a[0] for a in w.answers[:8]] == [0, 2, 4, 6, 1, 3, 5, 7]


def test_a_starved_queue_shows_in_the_tail():
    def tail(starve):
        clock = Clock()
        svc = FakeService(batch=4, exec_s=1.0, clock=clock, starve=starve)
        w = window.drive(svc, "c", np.zeros((64, 2), np.float32), _preds(64), CLOSED, 5.5,
                         clock=clock)
        assert w.lost == 0 and w.rejected == 0
        return w, run.p95(w.latencies_s + w.open_ages_s, w.rejected)

    fair, p95_fair = tail(None)
    starved, p95_starved = tail(4)
    # the four-term callers are never served: they wait out the whole window
    assert set(starved.step_shape) == {0} and max(starved.open_ages_s) == starved.seconds
    assert p95_fair == 3.0 and p95_starved == 6.0
    # counting only the answered requests would have hidden the stall
    assert run.p95(starved.latencies_s, 0) == 1.0


def test_only_a_closed_loop_is_driven():
    with pytest.raises(ValueError, match="closed loop"):
        window.drive(FakeService(4, 1.0, Clock()), "c", np.zeros((8, 2), np.float32),
                     _preds(8), {"arrivals": {"kind": "open", "rate_qps": 1.0}}, 1.0)


def test_p95_counts_failures_as_missing():
    assert run.p95([float(i) for i in range(1, 101)], 0) == 95.0
    assert run.p95([1.0] * 19, 1) == 1.0
    assert run.p95([1.0] * 18, 2) == float("inf")
