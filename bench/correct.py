"""What decides ``correct``: the served tokens against the reference.

After the window, a sample of the requests that were served (drawn
from the seed, always with the longest one and one of each traffic
class in it, so a class that takes another path, such as a full
prefix hit that copies a page on write, is always compared) is run
through the plain float32 reference of the configuration's family
(``logits`` of ``bench/families/<arch>.py``) over each prompt and its
served tokens, teacher-forced.  The number compared is the widest
gap by which a served token's reference logit lies below the
reference's best logit at that position: 0 where every served token is
the reference's own greedy choice.  Greedy serving in bfloat16 picks a
different token only where two logits lie within its rounding of each
other, so its gaps stay small; a wrong page, head or cache entry picks
tokens the reference ranks far below its best.

The limit is the cell's own (``bench/limits/<cell>.json``), set from
sound runs of the program (the lower reading) and from the float8
control (the upper reading); ``PERF.md`` gives both.  The control goes
through the same ``check``, with the tokens it puts first in the place
of the served ones (``reference.control_gaps``).
"""
from __future__ import annotations

from typing import Callable, Dict, List, Tuple

import numpy as np

from bench import reference

# (prompt, served tokens, traffic class)
Served = Tuple[np.ndarray, List[int], int]


def collect(requests, group: Dict[int, int]) -> List[Served]:
    """Every request that was served at least one token, with its
    class (``group``: uid -> class)."""
    return [(np.asarray(r.prompt, np.int32), list(r.generated),
             group[r.uid])
            for r in sorted(requests, key=lambda r: r.uid) if r.generated]


def sample(served: List[Served], n: int, seed: int) -> List[Served]:
    """``n`` of them, drawn from the seed: the longest, then one of each
    class not yet in, then the rest."""
    if len(served) <= n:
        return list(served)
    rng = np.random.default_rng([int(seed), 2])
    pick = [max(range(len(served)),
                key=lambda i: len(served[i][0]) + len(served[i][1]))]
    for g in sorted({row[2] for row in served}):
        if g not in {served[i][2] for i in pick}:
            pick.append(int(rng.choice(
                [i for i, row in enumerate(served) if row[2] == g])))
    rest = [i for i in range(len(served)) if i not in pick]
    pick += list(rng.choice(rest, n - len(pick), replace=False))
    return [served[i] for i in sorted(pick)]


def batch(rows: List[Served], n: int, length: int):
    """(n, length) tokens and targets: ``targets[b, t]`` is the token
    served after position t, -1 where none was (padding rows too)."""
    tokens = np.zeros((n, length), np.int32)
    targets = np.full((n, length), -1, np.int32)
    for b, (prompt, gen, _) in enumerate(rows):
        seq = np.concatenate([prompt, np.asarray(gen, np.int32)])[:length]
        tokens[b, :len(seq)] = seq
        lo = len(prompt) - 1
        targets[b, lo:len(seq) - 1] = seq[lo + 1:]
    return tokens, targets


def check(seed: int, fam, d: dict, served: List[Served], n: int,
          length: int, limits: Dict[str, dict],
          gaps: Callable = reference.served_gaps) -> dict:
    """``fam`` is the family, ``d`` its ``dims``.  ``gaps``: the served
    tokens' (the program's), or the control's
    (``reference.control_gaps``) at the same positions."""
    rows = sample(served, n, seed)
    tokens, targets = batch(rows, n, length)
    out_of_vocab = int(((targets >= d["V"]) | (targets < -1)).sum())
    compared = int((targets >= 0).sum())
    value = (float(gaps(fam, seed, d, tokens, targets).max())
             if compared else float("nan"))
    lim = limits["max_logit_gap"]["limit"]
    ok = bool(compared > 0 and out_of_vocab == 0 and np.isfinite(value)
              and value <= lim)
    return {"correct": ok, "compared": compared, "requests": len(rows),
            "limits": {"max_logit_gap": {"value": value, "limit": lim},
                       "tokens_compared": {"value": compared,
                                           "limit": "> 0"},
                       "tokens_outside_vocab": {"value": out_of_vocab,
                                                "limit": 0}}}

