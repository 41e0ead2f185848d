"""The one general traffic generator: it reads a traffic file and drives
``Nimbus`` through ``SchedulingPayload``s, one request at a time.

A traffic file (``traffic/<name>.json``) names a ``driver`` and gives its
parameters.  The driver is a module of its own, ``drivers/<driver>.py``,
found by that name; it defines ``Driver``, a subclass of :class:`Driver`
below, which owns all that is particular to its kind of request: how a
request is prepared and timed, greedy R-Storm's answer to the same request
(the never-worse baseline), the searches a decision ran (for the annealer's
byte count), and the loss and quality numbers of a decision.  The harness
and the checks call these and never ask which driver they hold, so a new
kind of traffic is a new driver file plus a traffic file.

Everything is derived from ``--seed``: the same seed gives the same requests.
"""

from __future__ import annotations

import dataclasses
import importlib
import json
from pathlib import Path
from typing import Dict, List, Optional, Tuple

import numpy as np

from . import model, reference

HERE = Path(__file__).resolve().parent


def load_traffic(name: str) -> dict:
    return json.loads((HERE / "traffic" / f"{name}.json").read_text())


def request_seed(seed: int, i: int) -> int:
    """Search seed of request ``i``: a hash of (``--seed``, i) in [0, 2**31)."""
    state = np.random.SeedSequence([int(seed) % (1 << 64), int(i) % (1 << 64)]).generate_state(1)
    return int(state[0] & 0x7FFFFFFF)


@dataclasses.dataclass
class Decision:
    """One timed Nimbus call and what it left on the cluster."""

    seconds: float
    #: topology id -> placements of every tenant after the decision.
    placements: Dict[str, Dict[str, str]]
    #: the topology id the decision placed; None where it placed no one tenant.
    decided: Optional[str] = None
    #: tenant index in the configuration of each topology id.
    tenant_of: Dict[str, int] = dataclasses.field(default_factory=dict)
    reported_cost: Optional[float] = None
    #: nodes that were dead when the decision was made.
    dead: tuple = ()
    #: greedy R-Storm's answer to the same request (same ids), where the
    #: driver takes it in the loop, and the tasks it left unplaced.
    greedy: Optional[Dict[str, Dict[str, str]]] = None
    greedy_unplaced: int = 0
    unplaced: int = 0


def payload(cfg: dict, tenant: int, scheduler: dict, topology_id: Optional[str] = None):
    from repro.api import SchedulingPayload

    return SchedulingPayload.from_dict(model.payload_dict(cfg, tenant, scheduler, topology_id))


def with_seed(scheduler: dict, seed: int) -> dict:
    kwargs = dict(scheduler.get("kwargs", {}))
    kwargs["seed"] = seed
    return {"name": scheduler["name"], "kwargs": kwargs}


class Driver:
    """A closed loop of one kind of request.  Subclasses set up in
    ``__init__`` and define :meth:`prepare`, :meth:`decide` and
    :meth:`greedy`; the rest has defaults for a netcost search."""

    #: Requests per whole turn of the traffic: the window closes on a whole
    #: number of turns.
    cycle = 1

    def __init__(self, cfg: dict, spec: dict, seed: int):
        self.cfg, self.spec, self.seed = cfg, spec, seed

    def warm_indices(self) -> List[int]:
        """Request indices run in set-up, one per shape the window uses."""
        return [-1]

    def prepare(self, i: int):
        """Untimed work before request ``i``; its result goes to decide."""
        raise NotImplementedError

    def decide(self, i: int, prepared) -> Decision:
        """Request ``i``, timed from the Nimbus call to its placement."""
        raise NotImplementedError

    def greedy(self, d: Decision) -> Tuple[Dict[str, Dict[str, str]], int]:
        """Greedy R-Storm's placements of every tenant on the same request,
        and the tasks it left unplaced."""
        raise NotImplementedError

    def searches(self, d: Decision) -> list:
        """``(topology spec, chains, steps)`` of the searches ``d`` ran."""
        kw = self.spec["scheduler"]["kwargs"]
        return [(self.cfg["tenants"][d.tenant_of[d.decided]], kw["n_chains"], kw["steps"])]

    def netcost(self, topos, cluster, placements) -> float:
        """Cluster-wide network cost, by the plain reference."""
        return sum(
            reference.network_cost(topos[t], cluster, placements[t], self.cfg["net_distance"])
            for t in sorted(placements)
        )

    def judge(self, d: Decision, topos, cluster, greedy) -> Tuple[float, Dict[str, float]]:
        """The relative loss of ``d`` against greedy in the objective the
        request names, and the decision's quality numbers.  The default is
        a netcost search's: loss and ``placed_netcost`` by the reference."""
        cost = self.netcost(topos, cluster, d.placements)
        base = self.netcost(topos, cluster, greedy)
        return (cost - base) / base, {"placed_netcost": cost}


def make_driver(cfg: dict, spec: dict, seed: int) -> Driver:
    module = importlib.import_module(f"{__package__}.drivers.{spec['driver']}")
    return module.Driver(cfg, spec, seed)
