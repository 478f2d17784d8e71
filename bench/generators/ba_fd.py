"""Barabasi-Albert growth as a fully dynamic stream (MoSSo Sect. 4.1).

Parameters: ``n_nodes`` nodes, ``m`` edges per new node, ``delete_p`` the
Sect. 4.1 deletion probability, ``delete_horizon`` the most insertions
between an edge's insertion and its deletion.  The stream holds every edge
of the graph, in random order, each deleted with probability ``delete_p``
at a later position; it ends there.  Labels are the node numbers (Python
ints).
"""
from __future__ import annotations

import random

from benchlib import fd


class Stream(fd.Stream):
    def __init__(self, params: dict, seed: int) -> None:
        edges = fd.ba_grow(int(params["n_nodes"]), int(params["m"]),
                           random.Random(f"ba_fd/{seed}/growth"))
        super().__init__(fd.fully_dynamic(
            edges, float(params["delete_p"]),
            random.Random(f"ba_fd/{seed}/order"),
            int(params["delete_horizon"])))
