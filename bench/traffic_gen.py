"""The one generator behind every traffic file under ``bench/traffic/``.

A traffic file is data: the loop it runs under (``bench/loops/<loop>.py``),
the backend the clients ask for, and the sizes of the mix.  Everything
random is drawn from ``--seed`` through numpy's ``SeedSequence``, per
stream, so a seed fixes every input whatever the timing: circuit ``i`` of
a serial run, and step ``k`` of client ``c``, get the same parameters in
every run.
"""
from __future__ import annotations

import numpy as np

STREAM_ANGLES, STREAM_SAMPLE, STREAM_CLIENT = 0, 1, 2


def seed_words(seed: int) -> list:
    """``--seed`` (any whole number) as non-negative 32-bit words."""
    s = int(seed) % (1 << 64)
    return [s & 0xFFFFFFFF, s >> 32]


def rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence(
        seed_words(seed) + list(stream)))


class Instances:
    """Parameters of circuit ``i``: the circuit family's random instance
    (``family.instance(cfg, generator)``) drawn from the seed and ``i``."""

    def __init__(self, seed: int, cfg: dict, family):
        self.seed, self.cfg, self.family = seed, cfg, family

    def __call__(self, i: int) -> np.ndarray:
        return self.family.instance(self.cfg,
                                    rng(self.seed, STREAM_ANGLES, i))


class ClientParams:
    """Parameters of step ``k`` of client ``c``: a uniform start in
    ``traffic["init"]`` and then a Gaussian step of ``step_sigma`` per
    request, as an optimizer's iterates move."""

    def __init__(self, seed: int, num_params: int, traffic: dict):
        self.seed, self.num_params = seed, num_params
        self.low, self.high = traffic["init"]
        self.sigma = traffic["step_sigma"]

    def start(self, client: int):
        """``(generator, first parameters)`` of one client."""
        g = rng(self.seed, STREAM_CLIENT, client)
        return g, g.uniform(self.low, self.high, self.num_params)

    def step(self, g: np.random.Generator, prev: np.ndarray) -> np.ndarray:
        return prev + g.normal(0.0, self.sigma, self.num_params)


class Reservoir:
    """A uniform sample of ``k`` items from a stream of unknown length,
    drawn from the seed (algorithm R)."""

    def __init__(self, k: int, seed: int):
        self.k, self.items, self.seen = k, [], 0
        self._rng = rng(seed, STREAM_SAMPLE)

    def offer(self, item) -> None:
        self.seen += 1
        if len(self.items) < self.k:
            self.items.append(item)
            return
        j = int(self._rng.integers(0, self.seen))
        if j < self.k:
            self.items[j] = item
