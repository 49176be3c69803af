"""Seeded stand-ins for the CAMEO paper's Table-1 datasets.

A copy of the program's generator (``repro.data.synthetic``), kept here so
that the benchmark's inputs cannot move with the program.  Each generator
reproduces the length, sampling granularity, seasonal periods and noise of
its dataset; the values are drawn from ``seed`` alone.
"""
from __future__ import annotations

import zlib

import numpy as np

# name -> (Table-1 length, ACF lags, kappa)
DATASETS = {
    "elec_power": (2976, 48, 1),
    "min_temp": (3650, 365, 1),
    "pedestrian": (8760, 24, 1),
    "uk_elec": (17520, 48, 1),
    "aus_elec": (230688, 7, 48),
    "humidity": (397440, 24, 60),
    "ir_bio_temp": (878400, 24, 60),
    "solar": (986160, 24, 120),
}


def sub_seed(seed: int, *path: int) -> int:
    """A 32-bit data seed for one stream of a run, drawn from the run's
    ``seed`` (any non-negative integer) and the stream's position."""
    return int(np.random.SeedSequence([int(seed), *map(int, path)])
               .generate_state(1)[0])


def _season(t, period, harmonics=2):
    out = np.zeros_like(t, dtype=np.float64)
    for h in range(1, harmonics + 1):
        out += np.cos(2 * np.pi * h * t / period) / h
    return out


def _ar1(rng, n, phi=0.7, sigma=1.0):
    from scipy.signal import lfilter
    e = rng.standard_normal(n) * sigma
    return lfilter([1.0], [1.0, -phi], e)


def make_series(name: str, seed: int, length: int | None = None) -> np.ndarray:
    """float64 stand-in of dataset ``name`` (Table-1 length by default)."""
    n = length or DATASETS[name][0]
    rng = np.random.default_rng(seed + zlib.crc32(name.encode()) % 65536)
    t = np.arange(n, dtype=np.float64)
    if name == "elec_power":
        x = 1.2 + 0.8 * _season(t, 96) + 0.3 * _ar1(rng, n, 0.6, 0.4)
        x += (rng.random(n) < 0.02) * rng.exponential(2.0, n)
        return np.maximum(x, 0.05)
    if name == "min_temp":
        return 11.0 + 6.0 * _season(t, 365.25, 1) + _ar1(rng, n, 0.7, 1.6)
    if name == "pedestrian":
        base = 400 + 380 * _season(t, 24) + 150 * _season(t, 168, 1)
        return np.round(np.maximum(base + _ar1(rng, n, 0.5, 90.0), 0.0))
    if name == "uk_elec":
        return (27000 + 5200 * _season(t, 48) + 1500 * _season(t, 336, 1)
                + _ar1(rng, n, 0.85, 450.0))
    if name == "aus_elec":
        return (6800 + 1100 * _season(t, 48) + 400 * _season(t, 336, 1)
                + _ar1(rng, n, 0.8, 120.0))
    if name == "humidity":
        x = 76 + 15 * _season(t, 1440) + _ar1(rng, n, 0.95, 0.8)
        return np.clip(x, 10.0, 100.0)
    if name == "ir_bio_temp":
        return (23 + 7.5 * _season(t, 1440) + 2.0 * _season(t, 1440 * 30, 1)
                + _ar1(rng, n, 0.9, 0.5))
    if name == "solar":
        day = 2880
        phase = (t % day) / day
        daylight = np.clip(np.sin(np.pi * (phase - 0.25) / 0.5), 0.0, None)
        cloud = np.clip(1.0 - 0.35 * np.abs(_ar1(rng, n, 0.98, 0.12)),
                        0.1, 1.0)
        x = 110.0 * daylight * cloud
        x[x < 1.0] = 0.0
        return x
    raise KeyError(name)
