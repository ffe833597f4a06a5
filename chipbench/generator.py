"""The one generator that turns a traffic mix's data file into work.

A mix is ``chipbench/traffic/<name>.json``.  Its ``kind`` says how work
arrives; the rest are parameters:

- ``closed_stream``: one caller solves problems back to back.  Sizes come
  in rounds, each round every size of the configuration once, in an order
  drawn from the seed, so every seed solves the same mix of sizes.
- ``open_loop``: independent users send requests at ``rate_per_s``, with
  exponential gaps (Poisson arrivals), whether or not earlier requests
  have finished.  A run has three phases: ``ramp_s`` to reach steady
  state, the measured window, and ``tail_s`` during which the load goes
  on while the window's last requests finish.  Each phase sends
  ``rate_per_s`` times its length in requests, with prompt and output
  lengths at evenly spaced quantiles of their clipped lognormal
  distributions (given by mean and sigma), paired by a fixed shuffle, and
  gaps at evenly spaced quantiles of the exponential, scaled to the
  phase's length.  The seed draws the order of lengths and of gaps in
  each phase, and the prompts' token ids.  So every seed sends the same
  requests in each phase, in another order and at other times.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Iterator, List, Mapping, Sequence

import numpy as np

from common import host_rng


# ------------------------------------------------------------ closed stream --
def expect(mix: Mapping[str, Any], kind: str) -> None:
    """Each kind of configuration takes the mixes of its own kind only."""
    if mix["kind"] != kind:
        raise ValueError(f"a {mix['kind']!r} mix where {kind!r} is driven")


def problem_sizes(sizes: Sequence[int], seed: int) -> Iterator[int]:
    """Endless problem sizes: rounds of every size, shuffled per round."""
    rng = host_rng(seed, "problem_sizes")
    while True:
        for i in rng.permutation(len(sizes)):
            yield int(sizes[i])


# ---------------------------------------------------------------- open loop --
PHASES = ("ramp", "window", "tail")


def _quantiles(count: int) -> np.ndarray:
    return (np.arange(count) + 0.5) / count


def quantile_lengths(spec: Mapping[str, Any], count: int) -> np.ndarray:
    """``count`` lengths at the midpoints of ``count`` equal slices of a
    lognormal of the given mean and sigma, clipped to [min, max]."""
    from statistics import NormalDist
    if spec["dist"] != "lognormal":
        raise ValueError(f"no lengths drawn from {spec['dist']!r}")
    z = np.array([NormalDist().inv_cdf(float(q)) for q in _quantiles(count)])
    median = spec["mean"] * np.exp(-spec["sigma"] ** 2 / 2)
    raw = median * np.exp(spec["sigma"] * z)
    return np.clip(np.rint(raw), spec["min"], spec["max"]).astype(np.int64)


@dataclass(frozen=True)
class Arrival:
    at_s: float                 # seconds after the schedule starts
    prompt: np.ndarray          # int32 token ids
    max_new_tokens: int
    phase: str                  # "ramp", "window" or "tail"


def phase_seconds(mix: Mapping[str, Any], seconds: float) -> List[float]:
    return [float(mix["ramp_s"]), float(seconds), float(mix["tail_s"])]


def schedule(mix: Mapping[str, Any], vocab: int, seed: int,
             seconds: float) -> List[Arrival]:
    """Every request of a run whose window lasts ``seconds``, in the
    order sent."""
    rate = float(mix["rate_per_s"])
    rng = host_rng(seed, "arrivals")
    out: List[Arrival] = []
    start = 0.0
    for phase, length in zip(PHASES, phase_seconds(mix, seconds)):
        count = max(1, int(round(rate * length)))
        prompts = quantile_lengths(mix["prompt_tokens"], count)
        outputs = quantile_lengths(mix["output_tokens"], count)
        outputs = outputs[np.random.default_rng(0).permutation(count)]
        gaps = -np.log1p(-_quantiles(count)) / rate
        gaps = rng.permutation(gaps) * (length / gaps.sum())
        at = start + np.cumsum(gaps) - gaps
        for t, i in zip(at, rng.permutation(count)):
            out.append(Arrival(
                at_s=float(t),
                prompt=rng.integers(0, vocab, int(prompts[i]),
                                    dtype=np.int32),
                max_new_tokens=int(outputs[i]), phase=phase))
        start += length
    return out


def longest_request(mix: Mapping[str, Any]) -> int:
    """Prompt plus output tokens of the longest request a mix can send."""
    return int(mix["prompt_tokens"]["max"] + mix["output_tokens"]["max"])
