"""The one traffic generator: a mix file (``traffic/<mix>.json``) and a
seed -> the requests of a run.

Every seed gets the same schedule: the sizes, classes and arrival
times, in one order, all drawn from the mix's own ``size_seed``.  The
run's seed draws the tokens (and ``bench/model.py`` the weights).  So
two seeds give the same work, and the spread between runs is the
system's, not the draw's: with the order permuted per seed, which
requests finished inside the window moved the chat cell's tokens per
second by 3% from seed to seed.

Mix keys:

* ``arrivals``: the name of the arrival process,
  ``bench/arrivals/<name>.py``, whose ``dues(mix, seconds, rng)`` gives
  each window request's due time in seconds after the window opens
  (``backlog``: all at 0, a queue that waits from the start;
  ``poisson``: an open loop at ``rate_per_s``).  A new process is a new
  file there.
* ``classes``: each ``{"share", "prompt": {"lo", "hi"}, "output":
  {"lo", "hi", "alpha"?}, "system_prompt"?}``.  Prompt lengths are
  uniform on [lo, hi]; outputs are bounded-Pareto with tail index
  ``alpha`` on [lo, hi] (uniform without ``alpha``).  A class with
  ``system_prompt`` prefixes its prompt with one of the mix's shared
  ``system_prompts`` (``{"count", "tokens"}``); with a prompt of 0 to 0
  tokens the request is the system prompt alone.
* ``initial_wave``: a class whose requests fill every slot before the
  window opens (prefilled during set-up).
* ``max_total``: cap on prompt + output tokens (the output is cut).

Set-up serves the warm-up waves in order: the initial wave, or each
system prompt once and then the first one again, which hits the prefix
cache in full and so copies its last page on write, as the window's
requests of the system prompt alone will.
"""
from __future__ import annotations

import dataclasses
from pathlib import Path
from typing import List, Optional

import numpy as np

from bench import load_module
from bench.model import ROOT

WARM = -1           # the class of warm-up requests


@dataclasses.dataclass
class Spec:
    uid: int
    prompt: np.ndarray
    max_new: int
    due: Optional[float] = None     # seconds after the window opens
    cls: int = 0


def _lengths(rng, spec: dict, n: int) -> np.ndarray:
    lo, hi = int(spec["lo"]), int(spec["hi"])
    if "alpha" not in spec:
        return rng.integers(lo, hi + 1, n)
    a = float(spec["alpha"])
    u = rng.random(n)
    x = lo / (1.0 - u * (1.0 - (lo / hi) ** a)) ** (1.0 / a)
    return np.clip(np.floor(x), lo, hi).astype(np.int64)


def _counts(shares, n: int) -> List[int]:
    """Largest-remainder split of n by shares."""
    raw = np.asarray(shares, float) / sum(shares) * n
    out = np.floor(raw).astype(int)
    for i in np.argsort(-(raw - out))[: n - out.sum()]:
        out[i] += 1
    return out.tolist()


def _sizes(rng, classes, n, max_total):
    """(class, prompt tail, output, system prompt index) for n requests."""
    rows = []
    for c, (cls, k) in enumerate(zip(classes, _counts(
            [x["share"] for x in classes], n))):
        p = _lengths(rng, cls["prompt"], k)
        o = _lengths(rng, cls["output"], k)
        sp = (rng.integers(0, 1 << 30, k) if cls.get("system_prompt")
              else np.full(k, -1))
        rows += [(c, int(a), int(b), int(s)) for a, b, s in zip(p, o, sp)]
    return rows


def generate(mix: dict, seed: int, seconds: float, slots: int,
             vocab: int, root: Path = ROOT):
    """-> (warm-up waves, window specs), each wave a list of specs.
    Window specs carry their due time."""
    size_rng = np.random.default_rng(int(mix["size_seed"]))
    tok_rng = np.random.default_rng([int(seed), 1])
    max_total = int(mix["max_total"])
    classes = mix["classes"]
    sys_spec = mix.get("system_prompts")
    system = ([tok_rng.integers(0, vocab, sys_spec["tokens"])
               .astype(np.int32) for _ in range(sys_spec["count"])]
              if sys_spec else [])

    def make(rows, first_uid, dues, cls=None):
        specs = []
        for i, (c, p, o, sp) in enumerate(rows):
            tail = tok_rng.integers(0, vocab, p).astype(np.int32)
            prompt = (np.concatenate([system[sp % len(system)], tail])
                      if sp >= 0 else tail)
            o = max(1, min(o, max_total - len(prompt)))
            specs.append(Spec(first_uid + i, prompt, o, float(dues[i]),
                              c if cls is None else cls))
        return specs

    waves = []
    if "initial_wave" in mix:
        rows = _sizes(size_rng, [dict(mix["initial_wave"], share=1)],
                      slots, max_total)
        rows = [rows[i] for i in size_rng.permutation(len(rows))]
        waves.append(make(rows, 1 << 30, np.zeros(len(rows)), WARM))
    elif system:
        waves += [[Spec((1 << 30) + i, p, 2, 0.0, WARM)
                   for i, p in enumerate(system)],
                  [Spec((1 << 30) + len(system), system[0], 2, 0.0, WARM)]]

    process = load_module(root, "arrivals", mix["arrivals"])
    dues = process.dues(mix, seconds, np.random.default_rng(
        [int(mix["size_seed"]), 2]))
    rows = _sizes(size_rng, classes, len(dues), max_total)
    rows = [rows[i] for i in size_rng.permutation(len(rows))]
    return waves, make(rows, 0, dues)
