"""Plain reference of the signal-plane faults: which carbon samples
arrive, what the controller reads in their place, which epochs the power
meter misses and which migrations fail.

Written from the configuration's `layers.faults` and the rules its
modules state (the fault plan's draw rule, the degradation ladder), with
plain NumPy; it imports nothing of the program.

Draw rule: each fault class draws from its own PCG64 stream, seeded at
the fault seed plus the class's salt (`layers.faults.salts`), so adding
one class leaves the others' draws as they were.
  dropouts   uniforms over the (T, R) samples in row order; a sample is
             lost where its uniform is below `dropout_prob`;
  gaps       uniforms over the T epochs; the meter misses an epoch where
             its uniform is below `gap_prob`;
  migration  uniforms over the (T, N) (epoch, container) pairs in row
             order; an attempted move fails where its uniform is below
             `fail_prob`.
Blackouts ((region or -1 for all, start, n)) and stated gaps ((start, n))
cover [start, start + n); `stale_every` k delivers only every k-th epoch.
The reference models a feed without noise windows.

The ladder, per (epoch, region), with the age counted in epochs since the
last received sample:
  fresh   a sample arrived: read it;
  hold    the last received sample while its age is at most `ttl_epochs`;
  prior   the mean of the samples received so far in this slot of the day
          (the running mean of all received samples where the slot has
          none) while the age is at most `prior_ttl_epochs`;
  floor   `c_max`, also before any sample has arrived.
A sample received at t is read at t and joins the state after it.
"""
from __future__ import annotations

import numpy as np

FRESH, HOLD, PRIOR, FLOOR = 0, 1, 2, 3
NEVER = 1_000_000          # the age of a region that never reported


def _rng(f: dict, seed: int, cls: str):
    return np.random.default_rng(int(seed) + int(f["salts"][cls]))


def _cols(region: int, R: int):
    return slice(None) if region < 0 else slice(region, region + 1)


def feed_mask(f: dict, seed: int, T: int, R: int) -> np.ndarray:
    """(T, R) bool: which samples arrive."""
    c = f["carbon"]
    if c["noise_windows"]:
        raise ValueError("the reference models a feed without noise")
    fresh = np.ones((T, R), dtype=bool)
    if c["stale_every"] > 1:
        fresh &= (np.arange(T) % int(c["stale_every"]) == 0)[:, None]
    if c["dropout_prob"] > 0.0:
        fresh &= (_rng(f, seed, "dropout").random((T, R))
                  >= c["dropout_prob"])
    for region, start, n in c["blackouts"]:
        fresh[max(0, start):start + n, _cols(region, R)] = False
    return fresh


def observe(f: dict, seed: int, true: np.ndarray, interval_s: float) -> dict:
    """What the controller reads of the (T, R) true intensity under the
    ladder: `observed`, with each sample's `tier` and `age`."""
    d = f["degrade"]
    if d["mode"] != "ladder":
        raise ValueError(f"the reference models the ladder, not "
                         f"{d['mode']!r}")
    T, R = true.shape
    fresh = feed_mask(f, seed, T, R)
    period = max(1, int(round(24 * 3600.0 / interval_s)))
    observed = np.empty((T, R))
    tier = np.empty((T, R), dtype=np.int64)
    age = np.empty((T, R), dtype=np.int64)
    last = np.zeros(R)
    since = np.full(R, NEVER)
    slot_sum = np.zeros((period, R))
    slot_n = np.zeros((period, R), dtype=np.int64)
    run_sum = np.zeros(R)
    run_n = np.zeros(R, dtype=np.int64)
    for t in range(T):
        got = fresh[t]
        since = np.where(got, 0, np.minimum(since + 1, NEVER))
        s = t % period
        seen = run_n > 0
        overall = run_sum / np.maximum(run_n, 1)
        prior = np.where(slot_n[s] > 0, slot_sum[s] / np.maximum(slot_n[s], 1),
                         overall)
        hold = seen & (since <= d["ttl_epochs"])
        use_prior = seen & ~hold & (since <= d["prior_ttl_epochs"])
        observed[t] = np.where(got, true[t],
                               np.where(hold, last,
                                        np.where(use_prior, prior,
                                                 d["c_max"])))
        tier[t] = np.where(got, FRESH, np.where(hold, HOLD,
                                                np.where(use_prior, PRIOR,
                                                         FLOOR)))
        age[t] = since
        last = np.where(got, true[t], last)
        slot_sum[s] += np.where(got, true[t], 0.0)
        slot_n[s] += got
        run_sum += np.where(got, true[t], 0.0)
        run_n += got
    return {"observed": observed, "true": true, "tier": tier, "age": age}


def summary(sig: dict) -> dict:
    """The rows' `fault_*` readings of the feed."""
    tier, age = sig["tier"], sig["age"]
    n = max(tier.size, 1)
    true = sig["true"]
    rel = np.abs(sig["observed"] - true) / np.where(true > 0.0, true, 1.0)
    return {
        "fault_stale_frac": float(np.count_nonzero(tier != FRESH) / n),
        "fault_hold_frac": float(np.count_nonzero(tier == HOLD) / n),
        "fault_prior_frac": float(np.count_nonzero(tier == PRIOR) / n),
        "fault_floor_frac": float(np.count_nonzero(tier == FLOOR) / n),
        "fault_max_age": int(np.minimum(age, age.shape[0]).max()),
        "fault_obs_rel_err_mean": float(rel.mean()),
        "fault_obs_rel_err_max": float(rel.max()),
    }


def meter_gaps(f: dict, seed: int, T: int) -> np.ndarray:
    """(T,) 1.0 where the power meter misses the epoch, else 0.0."""
    p = f["power"]
    gap = np.zeros(T, dtype=bool)
    if p["gap_prob"] > 0.0:
        gap |= _rng(f, seed, "gap").random(T) < p["gap_prob"]
    for start, n in p["gaps"]:
        gap[max(0, start):start + n] = True
    return gap.astype(np.float64)


def migration_failures(f: dict, seed: int, T: int, N: int) -> np.ndarray:
    """(T, N) bool: True where a move attempted at (epoch, container)
    fails."""
    p = f["migration"]["fail_prob"]
    if p <= 0.0:
        return np.zeros((T, N), dtype=bool)
    return _rng(f, seed, "migration").random((T, N)) < p
