"""Arithmetic shared by metric readers."""

from __future__ import annotations

import numpy as np


def latencies_ms(m) -> np.ndarray:
    lat = (m.rec["done"] - m.rec["due"]) / 1e6
    failed = m.rec["status"] != 200
    return np.where(failed, np.maximum(lat, m.request_timeout_s * 1e3), lat)


def latency_percentile(m, q: float):
    lat = latencies_ms(m)
    return float(np.percentile(lat, q)) if len(lat) else None


def share(part: float, whole: float):
    return 100.0 * part / whole if whole > 0 else None
