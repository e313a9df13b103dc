"""The one general generator of serving traffic. A traffic mix is a data file
(`benchmark/traffic/<mix>.json`) of parameters that this reads:

    arrivals:    {"process": "poisson", "rate_per_s": r}
                 {"process": "backlog", "keep_queued": n}         no clock
    prompt_len / output_len:
                 {"dist": "lognormal", "median": m, "sigma": s,
                  "min": a, "max": b}
                 {"dist": "uniform", "min": a, "max": b}
    drain_limit_s: how long after the window a request may take to
                 its first token before it counts as failed

Every seed gets the SAME lengths and the SAME gaps between arrivals: the
values are the quantile midpoints of the distribution (read off one large
sample drawn from a fixed seed). The run's seed draws their ORDER (which
prompt meets which answer length at which instant) and the token ids; no
request stops early. So seeds differ in order, never in the amount of work.
Order still matters to whoever waits: at 0.8 of the knee the tail of TTFT
over a window's requests swings with their order (PERF.md section 2), which
is why no TTFT statistic is an end-to-end metric yet.
"""
from __future__ import annotations

import numpy as np

_GRID_SEED = 20260927       # fixes the quantile grids; never the run's seed
_GRID_DRAWS = 256


def _draw(rng, spec, n):
    if spec["dist"] == "lognormal":
        x = np.exp(rng.normal(np.log(spec["median"]), spec["sigma"], n))
    elif spec["dist"] == "uniform":
        x = rng.uniform(spec["min"], spec["max"] + 1, n)
    elif spec["dist"] == "exponential":
        x = rng.exponential(1.0, n)
    else:
        raise ValueError(f"unknown distribution {spec['dist']!r}")
    return x


def quantile_grid(spec, n, salt):
    """n values that stand for the distribution: the means of n equal
    slices of one large sorted sample, drawn from a fixed seed."""
    rng = np.random.default_rng([_GRID_SEED, salt])
    x = np.sort(_draw(rng, spec, n * _GRID_DRAWS))
    return x.reshape(n, _GRID_DRAWS).mean(axis=1)


def _lengths(spec, n, salt):
    x = np.floor(quantile_grid(spec, n, salt)).astype(np.int64)
    return np.clip(x, spec["min"], spec["max"])


def make(traffic: dict, seed: int, seconds: float, vocab_size: int) -> dict:
    """-> {"mode": "open" | "backlog", "requests": [{"due", "prompt",
    "max_new_tokens"}], ...}; `due` is seconds from the window's start."""
    arr = traffic["arrivals"]
    rng = np.random.default_rng(int(seed))       # the order, the token ids
    if arr["process"] == "backlog":
        # enough that the queue never drains at any speed: the runner
        # releases them as lanes free, and stops at the window's end
        n = int(traffic["backlog_requests"])
        due = np.zeros(n)
    elif arr["process"] == "poisson":
        n = max(1, int(round(arr["rate_per_s"] * seconds)))
        gaps = quantile_grid({"dist": "exponential"}, n, 1)
        # the grid keeps the distribution's shape; its mean is set so the
        # n arrivals span exactly the window at the stated rate
        gaps *= (seconds / gaps.sum())
        due = np.cumsum(rng.permutation(gaps)) - gaps.mean() / 2
        due = np.clip(due, 0.0, None)
    else:
        raise ValueError(f"unknown arrival process {arr['process']!r}")
    p_len = rng.permutation(_lengths(traffic["prompt_len"], n, 2))
    o_len = rng.permutation(_lengths(traffic["output_len"], n, 3))
    requests = [{"due": float(due[i]),
                 "prompt": rng.integers(1, vocab_size, int(p_len[i])).tolist(),
                 "max_new_tokens": int(o_len[i])} for i in range(n)]
    return {"mode": "backlog" if arr["process"] == "backlog" else "open",
            "requests": requests,
            "keep_queued": int(arr.get("keep_queued", 0)),
            "drain_limit_s": float(traffic.get("drain_limit_s", 30.0))}
