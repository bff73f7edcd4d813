"""Published peaks of the chips the benchmark knows, keyed by jax's
``device_kind``. A kind that is not in ``peaks.json`` is an error, never a
default: a roofline share against the wrong peak is a wrong number."""

from __future__ import annotations

import json
import os
from typing import Dict

_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def peaks_for(device_kind: str) -> Dict[str, float]:
    with open(_PATH) as f:
        table = json.load(f)
    if device_kind.startswith("_") or device_kind not in table:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"add it to benchmark/peaks.json with its source")
    return table[device_kind]


def least_seconds(need: Dict[str, float], peaks: Dict[str, float]
                  ) -> Dict[str, float]:
    """The two roofline bounds of a demand (``bytes``, ``ops`` and the peak
    its operations run against) and which binds."""
    t_bytes = need["bytes"] / peaks["hbm_bytes_per_s"]
    t_ops = need["ops"] / peaks[need["ops_peak"]]
    return {"seconds": max(t_bytes, t_ops), "bytes_s": t_bytes,
            "ops_s": t_ops, "bound": "hbm" if t_bytes >= t_ops else "mxu"}
