"""Open-loop traffic from a mix file.

A mix (``chipbench/mixes/<name>.json``) is data: the length distributions
of prompts and outputs, and a ``shape_seed``. The rate it is offered at is
the cell's (``chipbench/cells/<workload>.json``), since it follows from
the model that serves it. The shapes of the requests (prompt and output
lengths) and the gaps between arrivals are drawn from ``shape_seed`` for
the number of requests the window holds; ``--seed`` draws only the token
ids. So every seed offers the same work on the same schedule: a run's
tails move with the system, not with the order a seed would give the long
prompts (on the chip, the order alone moved a TTFT tail by 14-21 % between
seeds against 2-9 % between two runs of one seed).

Arrivals are Poisson at the cell's rate: ``round(rate * seconds)``
requests whose exponential gaps are scaled to end inside the window, so the
offered rate is the same in every run of a given length.

Length distributions:

- ``{"dist": "lognormal", "median": m, "sigma": s, "min": a, "max": b}``
- ``{"dist": "uniform", "min": a, "max": b}``
"""
from __future__ import annotations

import dataclasses
import json
import math

import numpy as np


@dataclasses.dataclass
class Planned:
    """One request: ``rid``, ``due_s`` (seconds after the window opens),
    ``prompt`` (token ids) and ``max_new`` (tokens to generate)."""

    rid: int
    due_s: float
    prompt: tuple
    max_new: int


def load_mix(path) -> dict:
    with open(path) as f:
        return json.load(f)


def _lengths(rng, spec: dict, n: int) -> np.ndarray:
    kind = spec["dist"]
    if kind == "lognormal":
        x = spec["median"] * np.exp(spec["sigma"] * rng.standard_normal(n))
        return np.clip(np.rint(x), spec["min"], spec["max"]).astype(int)
    if kind == "uniform":
        return rng.integers(spec["min"], spec["max"] + 1, n)
    raise ValueError(f"unknown length distribution {kind!r}")


def max_length(spec: dict) -> int:
    """The longest length a distribution can give."""
    return spec["max"]


def count(rate: float, seconds: float) -> int:
    return max(1, round(rate * seconds))


def shapes(mix: dict, rate: float, seconds: float):
    """(prompt lengths, output lengths, gaps in seconds), in the order of
    arrival: the work every seed of this window length offers at ``rate``
    requests a second."""
    n = count(rate, seconds)
    rng = np.random.default_rng(mix["shape_seed"])
    prompts = _lengths(rng, mix["prompt"], n)
    outputs = _lengths(rng, mix["output"], n)
    gaps = rng.exponential(1.0 / rate, n)
    # the last request is due half a mean gap before the window closes
    end = seconds - 0.5 / rate
    gaps *= max(end, 0.0) / gaps.sum()
    return prompts, outputs, gaps


def plan(mix: dict, rate: float, seconds: float, seed: int,
         vocab: int) -> list[Planned]:
    """The run's requests: the mix's shapes and arrivals at ``rate``, with
    prompt token ids drawn from ``seed`` uniformly over ``vocab``."""
    prompts, outputs, gaps = shapes(mix, rate, seconds)
    rng = np.random.default_rng(int(seed) % (1 << 64))
    due = np.cumsum(gaps)
    return [Planned(rid=i, due_s=float(due[i]),
                    prompt=tuple(int(t) for t in rng.integers(0, vocab, n)),
                    max_new=int(outputs[i]))
            for i, n in enumerate(prompts)]


def buckets(lengths, block_size: int) -> list[int]:
    """The prefill lengths the engine pads these prompts to."""
    return sorted({block_size * math.ceil(n / block_size) for n in lengths})
