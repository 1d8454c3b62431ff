"""Backlog: a queue that is never empty.  The mix's ``requests``
requests are all due when the window opens, so they wait from the
start."""
import numpy as np


def dues(mix: dict, seconds: float, rng) -> np.ndarray:
    return np.zeros(int(mix["requests"]))
