"""The program's own spans of the traced decisions.

While the profiler captures, the program closes every ``repro.obs`` span
into a profile log (``repro.obs.profiled_spans()``: name, parent, wall
seconds).  A decision is a root span of that log whose tree holds a
``search.schedule``; churn's untimed greedy plans hold none and are left
out.  A program without the log gives nothing to read.
"""

from __future__ import annotations

from typing import Dict, Iterable, List, Optional, Tuple

#: The search's span whose presence marks a root span as a decision.
SEARCH = "search.schedule"


def _log():
    try:
        from repro.obs import profiled_spans
    except ImportError:
        return None
    return profiled_spans()


def decisions(n: int, spans: Optional[Iterable] = None) -> Optional[List[Tuple[object, list]]]:
    """The last ``n`` decisions as ``(root span, every span of its tree)``,
    in the order they closed; None when fewer than ``n`` (or none) are
    there.  ``spans`` defaults to the program's profile log."""
    spans = _log() if spans is None else list(spans)
    if not spans or n <= 0:
        return None
    children: Dict[int, list] = {}
    for sp in spans:
        if sp.parent is not None:
            children.setdefault(sp.parent, []).append(sp)
    out = []
    for root in spans:
        if root.parent is not None:
            continue
        tree, todo = [], [root]
        while todo:
            sp = todo.pop()
            tree.append(sp)
            todo.extend(children.get(sp.seq, ()))
        if any(sp.name == SEARCH for sp in tree):
            out.append((root, tree))
    return out[-n:] if len(out) >= n else None


def seconds_per_decision(n: int, names: Iterable[str], spans=None) -> Optional[float]:
    """Wall seconds of the spans named ``names`` in the last ``n``
    decisions, over ``n``."""
    found = decisions(n, spans)
    if found is None:
        return None
    names = set(names)
    return sum(sp.wall_s for _, tree in found for sp in tree if sp.name in names) / n
