"""``churn``: every tenant is submitted in set-up; request *i* kills the
oldest tenant and resubmits it under a fresh id with ``scheduler``, the
other tenants in place.  Greedy R-Storm's plan of the same payload on the
same cluster, taken untimed just before, is the baseline."""

from __future__ import annotations

import time
from typing import List

from .. import traffic


class Driver(traffic.Driver):
    def __init__(self, cfg, spec, seed):
        from repro.api import Nimbus

        super().__init__(cfg, spec, seed)
        self.nimbus = Nimbus()
        #: live (topology id, tenant index), oldest first.
        self.live: List[tuple] = []
        for k, t in enumerate(cfg["tenants"]):
            tid = f"{t['id']}-s{k}"
            scheduler = traffic.with_seed(spec["scheduler"], traffic.request_seed(seed, -1 - k))
            self.nimbus.submit(traffic.payload(cfg, k, scheduler, tid))
            self.live.append((tid, k))

    @property
    def cycle(self):
        return len(self.cfg["tenants"])

    def warm_indices(self):
        return [-2 - k for k in range(len(self.cfg["tenants"]))]

    def prepare(self, i):
        old, k = self.live.pop(0)
        self.nimbus.kill(old)
        tid = f"{self.cfg['tenants'][k]['id']}-{i}"
        greedy = self.nimbus.plan(traffic.payload(self.cfg, k, self.spec["greedy_scheduler"], tid))
        scheduler = traffic.with_seed(self.spec["scheduler"], traffic.request_seed(self.seed, i))
        return traffic.payload(self.cfg, k, scheduler, tid), tid, k, greedy

    def decide(self, i, prepared):
        payload, tid, k, greedy = prepared
        t0 = time.perf_counter()
        plan = self.nimbus.submit(payload)
        dt = time.perf_counter() - t0
        self.live.append((tid, k))
        placements = {t: dict(a.placements) for t, a in self.nimbus.state.assignments.items()}
        baseline = {t: p for t, p in placements.items() if t != tid}
        baseline[tid] = dict(greedy.placements)
        return traffic.Decision(
            seconds=dt,
            placements=placements,
            decided=tid,
            tenant_of={t: kk for t, kk in self.live},
            reported_cost=float(plan.network_cost),
            greedy=baseline,
            greedy_unplaced=len(greedy.unassigned),
            unplaced=len(plan.unassigned),
        )

    def greedy(self, d):
        return d.greedy, d.greedy_unplaced
