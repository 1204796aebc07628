"""The least inter-chip traffic of one gossip mixing step, from the graph
and the layout alone.

A ring of K nodes laid over M chips in contiguous blocks of K/M nodes
(node i on chip i // (K/M)) mixes v_k <- sum_l W_kl v_l. A chip must
receive the d-vector of every node outside it that neighbours one of its
own, once, whatever the program sends: for K = 16 on M = 4 chips, one node
from each of its two neighbour chips, 2 d floats. Each neighbour chip sends
over its own link, so the step takes at least the busiest link's bytes over
the link's rate (``ici_link_bytes_per_s`` in ``peaks.json``).
"""
from __future__ import annotations

from collections import Counter

from bench.counts import F32


def ring_inbound(k: int, m: int) -> dict:
    """{neighbour chip: node vectors it must send chip 0} in one mixing
    step of a ring of ``k`` nodes over ``m`` chips; every chip receives as
    much by the ring's symmetry."""
    block = k // m
    mine = set(range(block))
    outside = {(i + step) % k for i in mine for step in (-1, 1)} - mine
    return dict(Counter(node // block for node in outside))


def ring_mixing_bytes(k: int, m: int, d: int) -> int:
    """Bytes one chip must receive in one mixing step."""
    return sum(ring_inbound(k, m).values()) * d * F32


def ring_mixing_seconds(k: int, m: int, d: int,
                        link_bytes_per_s: float) -> float:
    """Least seconds of one mixing step: the busiest link's bytes over its
    rate."""
    return (max(ring_inbound(k, m).values(), default=0) * d * F32
            / link_bytes_per_s)
