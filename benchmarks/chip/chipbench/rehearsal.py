"""Tiny versions of the cells, for rehearsing a run on the CPU.

Same code path as a run on the chip (``cell.run``), with the fleet, the
pool, the engine geometry, the forests and the training sets cut so that
a CPU test finishes in seconds; the reference featurizes 4 chunks per
block. The front end (MSPCA, WPD) keeps its sizes. Used by the
benchmark's tests only.
"""

from __future__ import annotations

import copy
import pathlib

from chipbench import cell, reference


def tiny_spec(root: pathlib.Path, workload: str, spec: tuple | None = None
              ) -> tuple:
    bench, entry, cfg, traffic = spec or cell.load_spec(root, workload)
    cfg, traffic = copy.deepcopy(cfg), copy.deepcopy(traffic)
    reference.BLOCK_CHUNKS = 4
    cfg["pipeline"]["forest"].update(depth=3)
    if cfg["kind"] == "serve":
        cfg["engine"].update(max_batch=2, replay_depth=2)
        cfg["pipeline"]["forest"].update(n_trees=4)
        cfg["served_forest"].update(patients=2, chunks_per_class=1)
        cfg["check"]["sample_chunks"] = 4
        traffic["pool"] = {"timelines": 2, "interictal_chunks": 1}
        if traffic["arrivals"] == "closed":
            traffic.update(fleet=6, outstanding=2, backlog_chunks=[1, 2])
        else:
            traffic.update(rate=4, period_s=3)
            if traffic.get("burst"):
                traffic["burst"] = {"on_s": 0.5, "off_s": 0.5}
    else:
        cfg["patients"] = 1
        cfg["pipeline"]["forest"].update(n_trees=4)
        # Two chunks per shard, one of each class: every shard's
        # sub-forest sees both.
        cfg["training_set"] = {"interictal_windows": 240,
                               "preictal_windows": 240}
        traffic.update(check_fits=1, heldout_interictal_chunks=1)
    return bench, entry, cfg, traffic
