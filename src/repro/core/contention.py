"""Deprecated shim — the contention model now lives in :mod:`repro.network`.

The link-load engine is ``repro.network.routing`` (vectorized; the old
per-hop walker survives only as a test reference under
``tests/reference_dor.py``) and the traffic builders are
``repro.network.patterns``.  Existing imports keep working; new code should
import from ``repro.network`` directly.  See DESIGN.md.
"""

from __future__ import annotations

import warnings

# One-shot by module caching: Python executes this module (and hence the
# warning) once per process, however many times it is imported.
warnings.warn(
    "repro.core.contention is a deprecated re-export shim; import from "
    "repro.network instead (see DESIGN.md)",
    DeprecationWarning,
    stacklevel=2,
)

from repro.network.routing import (  # noqa: F401,E402
    LinkLoads,
    PairingPrediction,
    all_to_all_max_load,
    max_link_load,
    pairing_speedup,
    predict_pairing_time,
    route_dor,
    simulate_pattern,
)
from repro.network.geometry import uniform_offset_max_load  # noqa: F401,E402
from repro.network.patterns import (  # noqa: F401
    bisection_pairing,
    furthest_offset,
    pairing_pairs,
)

Coord = tuple

__all__ = [
    "Coord",
    "LinkLoads",
    "PairingPrediction",
    "all_to_all_max_load",
    "bisection_pairing",
    "furthest_offset",
    "max_link_load",
    "pairing_pairs",
    "pairing_speedup",
    "predict_pairing_time",
    "route_dor",
    "simulate_pattern",
    "uniform_offset_max_load",
]
