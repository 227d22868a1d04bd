"""The one generator of root traffic. A mix is a data file under
traffic/: the edge weights the tables carry, the root batch, how roots
are drawn and how the loop is closed. Nothing here knows a mix by name.
"""

from __future__ import annotations

import json
import os

import numpy as np

_ROOT_STREAM = 1 << 21


def load_traffic(bench_dir: str, name: str) -> dict:
    with open(os.path.join(bench_dir, "traffic", name + ".json")) as f:
        t = json.load(f)
    if t["loop"] != "closed":
        raise ValueError(f"traffic {name}: only a closed loop is built "
                         f"(got {t['loop']!r})")
    if t["roots"]["draw"] != "uniform":
        raise ValueError(f"traffic {name}: root draw "
                         f"{t['roots']['draw']!r} is not built yet")
    return t


class RootSource:
    """The graph facade NodeEstimator asks for roots: dense ids (row ==
    id), `sample_node(count)` drawn uniformly from the seed. A batch
    never holds one node twice, as an epoch-based loader's batches do
    not (and the activation cache's row scatter then has one writer per
    row)."""

    def __init__(self, n_nodes: int, edge_count: int, seed: int):
        self.node_count = int(n_nodes)
        self.edge_count = int(edge_count)
        self._rng = np.random.default_rng([int(seed), _ROOT_STREAM])

    def sample_node(self, count: int, node_type: int = -1) -> np.ndarray:
        if node_type >= 0:
            raise ValueError("the generated graph has no node types")
        if count > self.node_count:
            raise ValueError(f"{count} distinct roots of "
                             f"{self.node_count} nodes")
        out = np.empty(0, np.int64)
        while out.size < count:
            more = self._rng.integers(0, self.node_count,
                                      count - out.size + count // 8 + 8)
            both = np.concatenate([out, more])
            _, first = np.unique(both, return_index=True)
            out = both[np.sort(first)]     # draw order kept
        return out[:count].astype(np.uint64)
