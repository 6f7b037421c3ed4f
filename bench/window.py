"""The measured window: a closed loop of callers offered to the service.

The traffic file's ``arrivals`` is ``{"kind": "closed", "clients": n}``:
``n`` callers, each with one request outstanding, each sending its next
query as soon as its answer is delivered.  Every query is the next one of
the pool, so no two requests of a run are alike while the pool lasts.

The service is driven by ``step()``, which dispatches at most one
micro-batch and returns the answers delivered by it.  The window closes at
the end of the first step at or after ``seconds``: with requests queued the
service dispatches in every step, so that is a delivery.  Dispatch is
host-serial, so nothing is in flight then; each request still outstanding
at the close is
kept with its age at the close, a lower bound on its latency, so a caller
that the scheduler starves shows in the tail.
"""
from __future__ import annotations

import dataclasses
import time

import jax
import numpy as np


@dataclasses.dataclass
class Window:
    answers: list  # (pool index, ids, dists) in delivery order
    latencies_s: list  # one per answer
    seconds: float  # window start to the last delivery
    rejected: int  # submits refused by the service
    lost: int  # dispatched in the window and never answered
    queued_at_close: int  # never dispatched
    open_ages_s: list  # age at the close of each request still outstanding
    batches: int
    step_s: list  # duration of each step that delivered answers
    step_shape: list  # predicate shape (index into the mix) of each such step


def drive(svc, name: str, queries: np.ndarray, preds, traffic: dict, seconds: float,
          on_delivery=None, clock=time.perf_counter) -> Window:
    """Offer the traffic to ``svc`` for ``seconds``, using the pool's queries
    in order.  ``on_delivery(now, shape)`` is called after each step that
    delivered answers, with the predicate shape of its micro-batch (the
    traced run stops its trace there)."""
    from repro.core import predicate as P

    arrivals = traffic["arrivals"]
    if arrivals["kind"] != "closed":
        raise ValueError(f"unknown arrivals kind {arrivals['kind']!r}; the window drives "
                         "a closed loop")
    pool = len(queries)
    nxt = 0
    out: dict[int, tuple] = {}  # rid -> (pool index, submit time)
    answers, lat, step_s, step_shape = [], [], [], []
    rejected = batches = 0

    def submit():
        nonlocal nxt, rejected
        i = nxt % pool
        nxt += 1
        lo, hi = preds[i]
        t_sub = clock()
        with jax.profiler.TraceAnnotation("bench/submit"):
            rid = svc.submit(name, queries[i], P.Predicate(lo, hi))
        if isinstance(rid, int):
            out[rid] = (i, t_sub)
        else:
            rejected += 1

    t0 = clock()
    for _ in range(int(arrivals["clients"])):
        submit()
    while True:
        t_step = clock()
        with jax.profiler.TraceAnnotation("bench/step"):
            done = svc.step()
        now = clock()
        if not done and not svc.pending():
            break  # every caller's request was lost: nothing more can arrive
        with jax.profiler.TraceAnnotation("bench/deliver"):
            for r in done:
                i, t_sub = out.pop(r.rid)
                answers.append((i, r.ids, r.dists))
                lat.append(now - t_sub)
            if done:
                batches += 1
                step_s.append(now - t_step)
                step_shape.append(int(preds.kind[answers[-1][0]]))
                if on_delivery is not None:
                    on_delivery(now, step_shape[-1])
            if now - t0 >= seconds:
                break
            for _ in done:
                submit()
    queued = svc.pending()
    return Window(answers=answers, latencies_s=lat, seconds=now - t0, rejected=rejected,
                  lost=len(out) - queued, queued_at_close=queued,
                  open_ages_s=[now - t_sub for _, t_sub in out.values()], batches=batches,
                  step_s=step_s, step_shape=step_shape)
