"""2-tier fat tree (Clos) with ECMP, for the benchmark's plain reference.

A copy of the program's ``repro/sim/topology.py`` (full bisection, no
failed links): hosts -> ToR -> spine, all links the same speed.  Path
selection is ECMP, a deterministic hash of (src, dst, entropy) over the
uplinks.
"""
from __future__ import annotations

import dataclasses


def _mix(a: int, b: int, c: int) -> int:
    """Deterministic 32-bit hash mix (Knuth multiplicative + xors)."""
    h = (a * 2654435761) & 0xFFFFFFFF
    h ^= (b * 2246822519) & 0xFFFFFFFF
    h = (h * 3266489917) & 0xFFFFFFFF
    h ^= (c * 668265263) & 0xFFFFFFFF
    h = (h * 374761393) & 0xFFFFFFFF
    return (h >> 8) ^ (h & 0xFF)


@dataclasses.dataclass(frozen=True)
class FatTree:
    n_tor: int
    hosts_per_tor: int
    n_spine: int

    @property
    def n_hosts(self) -> int:
        return self.n_tor * self.hosts_per_tor

    def tor_of(self, host: int) -> int:
        return host // self.hosts_per_tor

    def ecmp_spine(self, src: int, dst: int, entropy: int) -> int:
        """ECMP: hash (src, dst, entropy) onto an uplink of src's ToR."""
        return _mix(src, dst, entropy) % self.n_spine
