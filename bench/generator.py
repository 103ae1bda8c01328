"""The one traffic generator: turns a mix file (``bench/traffic/<mix>.json``)
and a cell's parameters into the requests of one run.

Every seed does the same work, in the same order.  Lengths come in rounds
of ``round`` requests; each round holds the same stratified sample of the
mix's length distributions (the quantiles at ``(k + 0.5) / round``), in an
order drawn from the mix's own ``schedule_seed``, as are the arrival times.
The run's ``--seed`` draws only the prompt tokens (and the harness's
weights).  So a spread between seeds measures the system and not a
different load: which long prompt meets which burst, and so the tails of
a run, do not change with the seed.

Loops (``mix["loop"]``):

* ``open``: independent users; request ``i`` is due at ``arrival_s[i]``
  seconds after the window opens, whether or not earlier ones finished.
  The schedule starts ``lead_in_s`` (a cell parameter) before the window,
  so the engine is in its steady state when the window opens.  The mix's
  ``arrivals`` names the process (``ARRIVALS``), at the cell's
  ``rate_per_s``.
* ``closed``: a batch client that keeps ``waiting`` requests queued; the
  cell's ``requests`` bounds how many it can send.

Arrival processes (``{"process": ...}``): ``gamma``, a renewal process whose
gaps are gamma-distributed with mean ``1 / rate_per_s`` and coefficient of
variation ``cv`` (``cv`` 1 is Poisson; above 1, bursts as in BurstGPT).

Length distributions (``{"dist": ...}``): ``lognormal`` (``median``,
``sigma``) or ``uniform``, both clipped to ``[min, max]`` tokens.

Every request is greedy and its prompt is its own (no shared prefixes).  A
mix key, or a key of an arrival process or a distribution, that the
generator does not read is refused, so a mix cannot claim traffic it does
not make.
"""

from __future__ import annotations

import dataclasses
from statistics import NormalDist

import numpy as np


@dataclasses.dataclass
class Planned:
    """One request of the run as the client plans it."""

    prompt: np.ndarray  # [L] int32
    max_new_tokens: int
    arrival_s: float | None  # open loop: due time relative to window start


@dataclasses.dataclass
class Traffic:
    loop: str  # "open" | "closed"
    requests: list  # [Planned], in the order the client sends them
    waiting: int = 0  # closed loop: requests kept queued


MIX_KEYS = {
    "open": {"loop", "arrivals", "schedule_seed", "round", "prompt_tokens", "output_tokens"},
    "closed": {"loop", "waiting", "schedule_seed", "round", "prompt_tokens", "output_tokens"},
}
DIST_KEYS = {"lognormal": {"dist", "median", "sigma", "min", "max"},
             "uniform": {"dist", "min", "max"}}
ARRIVAL_KEYS = {"gamma": {"process", "cv"}}


def _keys(what: str, got: dict, want: set) -> None:
    if set(got) != want:
        raise ValueError(f"{what}: keys {sorted(got)}, the generator reads exactly "
                         f"{sorted(want)}")


def validate(mix: dict) -> None:
    """Refuse a mix that holds a key the generator does not read, or lacks
    one it needs."""
    if mix.get("loop") not in MIX_KEYS:
        raise ValueError(f"unknown loop {mix.get('loop')!r}; known: {sorted(MIX_KEYS)}")
    _keys(f"{mix['loop']} mix", mix, MIX_KEYS[mix["loop"]])
    for k in ("prompt_tokens", "output_tokens"):
        dist = mix[k].get("dist")
        if dist not in DIST_KEYS:
            raise ValueError(f"unknown length distribution {dist!r}; known: {sorted(DIST_KEYS)}")
        _keys(f"{k} ({dist})", mix[k], DIST_KEYS[dist])
    if "arrivals" in mix:
        proc = mix["arrivals"].get("process")
        if proc not in ARRIVAL_KEYS:
            raise ValueError(f"unknown arrival process {proc!r}; known: {sorted(ARRIVAL_KEYS)}")
        _keys(f"arrivals ({proc})", mix["arrivals"], ARRIVAL_KEYS[proc])


def quantile_lengths(spec: dict, m: int) -> np.ndarray:
    """The stratified sample of one round: ``m`` quantiles, clipped."""
    q = (np.arange(m) + 0.5) / m
    lo, hi = int(spec["min"]), int(spec["max"])
    if spec["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(p) for p in q])
        x = np.exp(np.log(spec["median"]) + spec["sigma"] * z)
    elif spec["dist"] == "uniform":
        x = lo + q * (hi - lo)
    return np.clip(np.rint(x), lo, hi).astype(np.int64)


def gamma_times(arrivals: dict, rate: float, start: float, end: float,
                rng: np.random.Generator) -> np.ndarray:
    """Arrival times on ``[start, end)`` of a renewal process whose gaps are
    gamma-distributed with mean ``1 / rate`` and coefficient of variation
    ``arrivals["cv"]``."""
    shape = 1.0 / float(arrivals["cv"]) ** 2
    out, t = [], start
    while True:
        t += rng.gamma(shape, 1.0 / (rate * shape))
        if t >= end:
            return np.asarray(out)
        out.append(t)


ARRIVALS = {"gamma": gamma_times}


def make_traffic(mix: dict, params: dict, seed: int, seconds: float, vocab: int) -> Traffic:
    """Requests for one run of ``seconds``; a pure function of its arguments."""
    validate(mix)
    fixed = np.random.default_rng(int(mix["schedule_seed"]))
    if mix["loop"] == "open":
        process = ARRIVALS[mix["arrivals"]["process"]]
        times = process(mix["arrivals"], float(params["rate_per_s"]),
                        -float(params["lead_in_s"]), float(seconds), fixed)
        n = len(times)
    else:
        times, n = None, int(params["requests"])
    m = int(mix["round"])
    prompt_q = quantile_lengths(mix["prompt_tokens"], m)
    output_q = quantile_lengths(mix["output_tokens"], m)
    prompt_lens, output_lens = [], []
    while len(prompt_lens) < n:
        prompt_lens.extend(fixed.permutation(prompt_q))
        output_lens.extend(fixed.permutation(output_q))
    run = np.random.default_rng(int(seed))
    reqs = [
        Planned(
            run.integers(1, vocab, int(prompt_lens[i]), dtype=np.int32),
            int(output_lens[i]),
            None if times is None else float(times[i]),
        )
        for i in range(n)
    ]
    return Traffic(mix["loop"], reqs, int(mix.get("waiting", 0)))


def reachable_prompt_lengths(mix: dict) -> np.ndarray:
    """Every prompt length any run of this mix can send."""
    return np.unique(quantile_lengths(mix["prompt_tokens"], int(mix["round"])))
