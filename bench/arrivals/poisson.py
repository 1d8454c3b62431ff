"""Open loop: Poisson arrivals at the mix's ``rate_per_s``, due at fixed
times whatever the system does.  The count is the rate times the run's
seconds, and given the count a Poisson process's arrival times are
uniform order statistics over the window."""
import numpy as np


def dues(mix: dict, seconds: float, rng) -> np.ndarray:
    n = int(round(float(mix["rate_per_s"]) * seconds))
    return np.sort(rng.uniform(0.0, seconds, n))
