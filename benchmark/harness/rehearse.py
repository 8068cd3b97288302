"""The size of a CPU rehearsal (``run.py --rehearse``): each configuration
file and each traffic file carries its own tiny size under a ``rehearse``
key, laid over the file's own keys here. A rehearsal walks the cell's driver
end to end on whatever platform is there and reports no metric: a CPU number
is never a device metric."""

from __future__ import annotations

import copy
from typing import Any, Dict, Tuple


def overlay(base: Dict[str, Any], over: Dict[str, Any]) -> Dict[str, Any]:
    """``over`` laid on ``base``; a nested group is laid over key by key."""
    out = copy.deepcopy(base)
    for k, v in over.items():
        if isinstance(v, dict) and isinstance(out.get(k), dict):
            out[k] = overlay(out[k], v)
        else:
            out[k] = copy.deepcopy(v)
    return out


def shrink(cfg: Dict[str, Any], traffic: Dict[str, Any]
           ) -> Tuple[Dict[str, Any], Dict[str, Any]]:
    for name, data in (("configuration", cfg), ("traffic", traffic)):
        if "rehearse" not in data:
            raise SystemExit(f"this {name} file has no `rehearse` sizes")
    return (overlay(cfg, cfg["rehearse"]),
            overlay(traffic, traffic["rehearse"]))
