"""Shared code of the chip benchmark: traffic generation, the plain
reference, trace reduction, the peaks table and the in-window compile
counter. Later cells, mixes and per-layer metrics add files beside it
(``configs/``, ``traffic/``, ``metrics/``) and need no edit here."""
