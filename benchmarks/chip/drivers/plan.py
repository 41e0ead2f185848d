"""``plan``: closed-loop ``Nimbus.plan`` of one tenant with ``scheduler``;
request *i* sets the scheduler's ``seed`` kwarg to ``request_seed(--seed, i)``.
Greedy R-Storm's plan of the same payload is the baseline; it has no seed,
so it is taken once."""

from __future__ import annotations

import time

from .. import traffic


class Driver(traffic.Driver):
    def __init__(self, cfg, spec, seed):
        from repro.api import Nimbus

        super().__init__(cfg, spec, seed)
        self.tenant = int(spec.get("tenant", 0))
        self.tid = cfg["tenants"][self.tenant]["id"]
        self.nimbus = Nimbus()
        self._greedy = None

    def prepare(self, i):
        scheduler = traffic.with_seed(self.spec["scheduler"], traffic.request_seed(self.seed, i))
        return traffic.payload(self.cfg, self.tenant, scheduler)

    def decide(self, i, payload):
        t0 = time.perf_counter()
        plan = self.nimbus.plan(payload)
        dt = time.perf_counter() - t0
        return traffic.Decision(
            seconds=dt,
            placements={self.tid: dict(plan.placements)},
            decided=self.tid,
            tenant_of={self.tid: self.tenant},
            reported_cost=float(plan.network_cost),
            unplaced=len(plan.unassigned),
        )

    def greedy(self, d):
        if self._greedy is None:
            from repro.api import Nimbus

            plan = Nimbus().plan(traffic.payload(self.cfg, self.tenant, self.spec["greedy_scheduler"]))
            self._greedy = ({self.tid: dict(plan.placements)}, len(plan.unassigned))
        return self._greedy
