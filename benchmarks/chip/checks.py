"""What decides ``correct``: every decision of the window, judged after it.

A run is correct when every number is at most its limit.  Three exact
comparisons with the limit 0:

* ``invalid`` — broken placements, counted over all decisions: tasks on a
  dead or unknown node, nodes over their memory capacity (the hard
  dimension, over every tenant on the cluster), and tasks left unplaced
  beyond what greedy R-Storm leaves on the same request;
* ``cost_gap`` — the largest gap between the network cost the program
  reports for a placement and the plain re-score of that placement
  (``reference.network_cost``); only for decisions that report one;
* ``worse`` — the largest relative loss against greedy R-Storm on the same
  request, in the objective the request names (the driver's ``judge``; a
  throughput loss counts only beyond ``reference_tp.TOLERANCE``);

and, only for decisions whose driver carries a reported throughput:

* ``tp_gap`` — the largest relative gap between the sink throughput the
  program reports for the decided topology and the plain fluid re-score of
  it alone on the cluster (``reference_tp.alone``), with the limit
  ``reference_tp.TOLERANCE``: ten times the 1e-9 within which the program's
  solve lies of its fixed point where it meets its stopping rule, and below
  what a float32 solve misses by.

The same pass averages the driver's quality numbers over the decisions
(``placed_netcost``; ``placed_tp`` too for a throughput request).
"""

from __future__ import annotations

from typing import Dict, List, Optional

from . import model, reference, reference_tp

LIMITS = {"invalid": 0.0, "cost_gap": 0.0, "worse": 0.0, "tp_gap": reference_tp.TOLERANCE}


def _topologies(cfg: dict, placements: Dict[str, Dict[str, str]], tenant_of: Dict[str, int]):
    out = {}
    for tid in placements:
        spec = dict(cfg["tenants"][tenant_of[tid]])
        spec["id"] = tid
        out[tid] = model.topology_from_spec(spec)
    return out


def judge(cfg: dict, driver, decisions: List) -> dict:
    """Returns ``{"numbers": {name: value}, "metrics": {name: mean value},
    "failed": decisions that moved a number}``."""
    base_cluster = model.cluster_from_config(cfg)
    invalid = 0
    cost_gap: Optional[float] = None
    tp_gap: Optional[float] = None
    worse = 0.0
    sums: Dict[str, float] = {}
    failed = 0
    for d in decisions:
        cluster = base_cluster.with_dead(d.dead)
        topos = _topologies(cfg, d.placements, d.tenant_of)
        merged = {k: v for p in d.placements.values() for k, v in p.items()}
        v = reference.violations(topos.values(), cluster, merged)
        greedy, greedy_unplaced = driver.greedy(d)
        broken = v["dead"] + v["over"] + max(0, d.unplaced - greedy_unplaced)
        gap = 0.0
        if d.reported_cost is not None:
            mine = reference.network_cost(
                topos[d.decided], cluster, d.placements[d.decided], cfg["net_distance"]
            )
            gap = abs(d.reported_cost - mine)
            cost_gap = gap if cost_gap is None else max(cost_gap, gap)
        tp_off = 0.0
        if d.reported_tp is not None:
            mine = reference_tp.alone(
                cfg, d.decided, d.placements[d.decided], d.tenant_of[d.decided], d.dead
            )
            tp_off = abs(d.reported_tp - mine) / mine if mine > 0 else abs(d.reported_tp)
            tp_gap = tp_off if tp_gap is None else max(tp_gap, tp_off)
        loss, quality = driver.judge(d, topos, cluster, greedy)
        for k, x in quality.items():
            sums[k] = sums.get(k, 0.0) + x
        invalid += broken
        worse = max(worse, loss)
        failed += broken > 0 or gap > 0 or loss > 0 or tp_off > LIMITS["tp_gap"]
    numbers = {"invalid": float(invalid), "worse": worse}
    if cost_gap is not None:
        numbers["cost_gap"] = cost_gap
    if tp_gap is not None:
        numbers["tp_gap"] = tp_gap
    metrics = {k: s / len(decisions) for k, s in sums.items()}
    return {"numbers": numbers, "metrics": metrics, "failed": failed}


def passed(numbers: Dict[str, float]) -> bool:
    return all(numbers[k] <= LIMITS[k] for k in numbers)
