"""Synthetic hourly carbon-intensity traces calibrated to region statistics.

c(t) = avg · max(floor, 1 + a·sin-diurnal(t-φ) + AR(1) noise)

The diurnal amplitude and noise scale are solved from the target CoV
(CoV² ≈ a²/2 + σ², sinusoid and AR(1) independent), so the generated trace
reproduces each region's (avg, CoV) (bench/tests/test_bench_gen.py).
"""
from __future__ import annotations

import zlib

import numpy as np

from bench.gen.regions import REGIONS, RegionStats


def synth_trace(region: str | RegionStats, hours: int = 24 * 30,
                seed: int = 0) -> np.ndarray:
    """Hourly g·CO₂e/kWh array of length `hours`."""
    r = REGIONS[region] if isinstance(region, str) else region
    # stable per-region salt: Python's str hash() is salted per process
    # (PYTHONHASHSEED), which made traces differ across runs
    rng = np.random.default_rng(seed + (zlib.crc32(r.name.encode()) % 100003))
    t = np.arange(hours, dtype=np.float64)
    # split target variance: 2/3 diurnal, 1/3 AR noise
    a = np.sqrt(2.0 * (r.cov ** 2) * 2.0 / 3.0)
    sigma = np.sqrt((r.cov ** 2) / 3.0)
    diurnal = -a * np.sin(2 * np.pi * (t - r.diurnal_phase_h + 6.0) / 24.0)
    rho = 0.9
    eps = rng.normal(0, sigma * np.sqrt(1 - rho ** 2), hours)
    ar = np.zeros(hours)
    for i in range(1, hours):
        ar[i] = rho * ar[i - 1] + eps[i]
    series = r.avg * np.maximum(0.05, 1.0 + diurnal + ar)
    return series


def trace_cov(series: np.ndarray) -> float:
    return float(np.std(series) / np.mean(series))
