"""The one traffic generator: reads a traffic file, makes the arrivals.

A traffic file (``chipbench/traffic/<name>.json``) holds parameters only.
One *cycle* of ``cycle_s`` stream-seconds is the unit of work; a run
repeats whole cycles.  Keys:

- ``kind``: ``frames`` (every arrival is one input frame) or ``sessions``
  (a closed population of decode sessions; arrivals are decode ticks);
- ``arrivals`` / ``ticks``: an arrival process of ``chipbench.arrivals``;
- ``link``: the bandwidth steps of one cycle, ``[{"at": s, "mbps": m}]``;
  the configuration maps each bandwidth to its split;
- ``strategy``: the repartition strategy used when the split changes;
- ``frames``: ``images``, how many distinct seeded images the frames cycle
  through;
- ``sessions``: ``population`` sessions whose prompt and answer lengths
  are the quantiles of a clipped lognormal (``mean`` or ``median``,
  ``sigma``, ``min``, ``max``).  Every seed gets the same lengths in the
  same order; the seed draws the prompt tokens, so seeds change the
  inputs and not the amount of work.
"""
from __future__ import annotations

import json
import math
from pathlib import Path
from statistics import NormalDist

import numpy as np

from chipbench import arrivals


def load(path) -> dict:
    return json.loads(Path(path).read_text())


def substream(seed: int, *key: int) -> np.random.Generator:
    """An independent generator per purpose, derived from the run seed."""
    return np.random.default_rng(np.random.SeedSequence(int(seed),
                                                        spawn_key=key))


def link_steps(traffic: dict, split_for_mbps: dict) -> list:
    """``[(at, mbps, split)]`` of one cycle."""
    out = []
    for step in traffic["link"]:
        mbps = float(step["mbps"])
        key = f"{mbps:g}"
        if key not in split_for_mbps:
            raise KeyError(f"configuration names no split for {key} Mbps")
        out.append((float(step["at"]), mbps, int(split_for_mbps[key])))
    return out


def arrival_times(traffic: dict, key: str, seed: int, cycle: int) -> list:
    spec = traffic[key]
    sub = int(substream(seed, 1, cycle).integers(2 ** 31))
    return arrivals.times(spec, float(traffic["cycle_s"]), seed=sub)


def frame_plan(traffic: dict, seed: int, cycle: int) -> list:
    """``[(t, image_index)]`` of one cycle's frames."""
    ts = arrival_times(traffic, "arrivals", seed, cycle)
    idx = substream(seed, 2, cycle).integers(0, int(traffic["images"]),
                                             size=len(ts))
    return list(zip(ts, (int(i) for i in idx)))


def lognormal_quantiles(spec: dict, n: int) -> list:
    """``n`` lengths at the mid-quantiles of a clipped lognormal, given by
    its median or by its mean (before the clip)."""
    nd, sigma = NormalDist(), float(spec["sigma"])
    median = (spec["median"] if "median" in spec
              else spec["mean"] * math.exp(-sigma * sigma / 2))
    out = []
    for i in range(n):
        z = nd.inv_cdf((i + 0.5) / n)
        v = int(round(median * math.exp(sigma * z)))
        out.append(min(max(v, int(spec["min"])), int(spec["max"])))
    return out


def spread_order(n: int) -> np.ndarray:
    """Indices ``0..n-1`` in an order whose every run of consecutive
    entries spreads evenly over the whole range (a golden-ratio sequence),
    so that every prefix of a population holds about the same mix of
    lengths."""
    return np.argsort((np.arange(n) * 0.6180339887498949) % 1.0,
                      kind="stable")


def session_specs(traffic: dict, seed: int, vocab_size: int) -> list:
    """The population: ``[(prompt_tokens, answer_len)]``.

    Prompt and answer lengths are paired by a fixed permutation, and
    sessions come in ``spread_order`` of their answer lengths, the same
    for every seed; the seed draws the token ids.  A run, which uses a
    prefix of the population, so does the same work on every seed."""
    n = int(traffic["population"])
    prompts = lognormal_quantiles(traffic["prompt"], n)
    answers = lognormal_quantiles(traffic["answer"], n)
    pair = np.random.default_rng(0).permutation(n)
    order = spread_order(n)
    rng = substream(seed, 4)
    return [(rng.integers(0, vocab_size, size=prompts[pair[j]]).astype(np.int32),
             answers[j]) for j in order]


def max_context(traffic: dict) -> int:
    """The longest context a session can reach: prompt plus answer."""
    return int(traffic["prompt"]["max"]) + int(traffic["answer"]["max"])
