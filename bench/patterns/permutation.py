"""A random derangement: every host sends one message and receives one.

A copy of the program's ``permutation_pairs`` (``repro/sim/
workloads.py``), so the yardstick does not move when the program does.
"""
import random


def flows(n_hosts: int, seed: int, msg_bytes: float) -> list:
    rng = random.Random(seed)
    while True:
        perm = list(range(n_hosts))
        rng.shuffle(perm)
        if all(perm[i] != i for i in range(n_hosts)):
            return [(i, perm[i], float(msg_bytes)) for i in range(n_hosts)]
