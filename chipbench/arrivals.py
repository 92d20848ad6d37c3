"""Seeded arrival processes, kept with the benchmark.

Copied from the program's ``repro.serving.workload`` (``uniform``,
``poisson``, ``bursty``) so that a change to the program cannot move the
yardstick.  Every process yields ascending arrival times in
``[start, start + duration)``, quantised to the nanosecond grid, and the
same ``(parameters, seed)`` always yields the same times.
"""
from __future__ import annotations

from typing import Iterator

import numpy as np

TICK_S = 1e-9


def quantize(t: float) -> float:
    return round(t / TICK_S) * TICK_S


def uniform(duration: float, *, rate: float, seed: int = 0,
            start: float = 0.0) -> Iterator[float]:
    """The paper's camera: one arrival every ``1/rate`` s (seed unused)."""
    if rate <= 0:
        raise ValueError(f"rate must be positive ({rate=})")
    i = 0
    while True:
        t = quantize(start + i / rate)
        if t >= start + duration - 1e-12:
            return
        yield t
        i += 1


def poisson(duration: float, *, rate: float, seed: int = 0,
            start: float = 0.0) -> Iterator[float]:
    """Exponential gaps at ``rate`` arrivals/s."""
    if rate <= 0:
        raise ValueError(f"rate must be positive ({rate=})")
    rng = np.random.default_rng(seed)
    t, end = start, start + duration
    while True:
        t += rng.exponential(1.0 / rate)
        if t >= end:
            return
        yield quantize(t)


def bursty(duration: float, *, rate_on: float, rate_off: float,
           mean_on: float, mean_off: float, seed: int = 0,
           start: float = 0.0) -> Iterator[float]:
    """Two-state MMPP: Poisson at ``rate_on`` inside exponential-dwell
    bursts and at ``rate_off`` between them; starts in the off state."""
    if rate_on <= 0 or rate_off < 0 or mean_on <= 0 or mean_off <= 0:
        raise ValueError("bad bursty parameters")
    rng = np.random.default_rng(seed)
    t, end = start, start + duration
    on = False
    state_end = start + rng.exponential(mean_off)
    while t < end:
        rate = rate_on if on else rate_off
        if rate <= 0.0:
            t = state_end
        else:
            nxt = t + rng.exponential(1.0 / rate)
            if nxt < state_end:
                t = nxt
                if t >= end:
                    return
                yield quantize(t)
                continue
            t = state_end
        on = not on
        state_end = t + rng.exponential(mean_on if on else mean_off)


PROCESSES = {"uniform": uniform, "poisson": poisson, "bursty": bursty}


def times(spec: dict, duration: float, *, seed: int = 0,
          start: float = 0.0) -> list:
    """Arrival times of ``spec`` (``{"process": name, **parameters}``)."""
    params = dict(spec)
    fn = PROCESSES[params.pop("process")]
    return list(fn(duration, seed=seed, start=start, **params))
