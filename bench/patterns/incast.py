"""``fan_in`` senders drawn from the seed, each sending one message to
host ``dst``.

A copy of the program's ``incast_scenario`` (``repro/sim/
workloads.py``), so the yardstick does not move when the program does.
"""
import random


def flows(n_hosts: int, seed: int, msg_bytes: float, fan_in: int,
          dst: int = 0) -> list:
    rng = random.Random(seed)
    candidates = [h for h in range(n_hosts) if h != dst]
    srcs = rng.sample(candidates, min(fan_in, len(candidates)))
    return [(s, dst, float(msg_bytes)) for s in srcs]
