"""Readers of the program's own spans (``repro.core.telemetry.span``),
shared by the metrics that read them.

Each returns None where the run recorded none of the spans it reads, as
a program without them records none.
"""
from __future__ import annotations

from typing import Optional

import numpy as np


def run_child_mean(record, *children: str) -> Optional[float]:
    """Mean per call of the summed ``run:<policy>/<child>`` spans: steps
    of the compiled pass, from each call's ``LAST_PHASES``."""
    calls, pol = record.get("calls"), record.get("policy")
    if not calls or pol is None:
        return None
    names = [f"run:{pol}/{c}" for c in children]
    if any(n not in c["phases"] for c in calls for n in names):
        return None
    return float(np.mean([sum(c["phases"][n] for n in names)
                          for c in calls]))


def per_route_ms(spans, child: str) -> Optional[float]:
    """Seconds in the ``route/<child>`` spans per ``route`` call, in ms,
    from :func:`repro.core.telemetry.snapshot`'s (count, seconds), over
    every route call wherever it was opened: a retry that ``drain``
    routes again is ``drain/route``, its child ``drain/route/<child>``."""
    calls = sum(c for k, (c, _) in spans.items()
                if k.rsplit("/", 1)[-1] == "route")
    secs = [s for k, (_, s) in spans.items()
            if k == f"route/{child}" or k.endswith(f"/route/{child}")]
    if not calls or not secs:
        return None
    return 1e3 * sum(secs) / calls
