"""Controls: runs that break a guarantee a configuration states.

A cell's file names its control, which must come out not correct.  A
control is either the program with one of its own options switched
(``{"program": {"lb_mode": "fixed"}}``: each flow pinned to one ECMP
path, so packet spraying is gone) or the reference with an engine
swapped in, named here.
"""
from __future__ import annotations

from bench.reference.engines import STrackSender


class NoRetransmitSender(STrackSender):
    """STrack without its reliable delivery: a packet declared lost is
    never sent again, so a message that lost one never completes."""

    __slots__ = ()

    def _declare_lost(self, psns) -> None:
        return None


SENDERS = {"no_retransmit": NoRetransmitSender}
