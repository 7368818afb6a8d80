"""Heroes' server merge (Eq. 5) in numpy, float64.

Basis: the mean over every client of the round.  Coefficient: block
``i`` becomes the mean of the clients that trained it this round; a block
that nobody trained keeps its value.  Hidden ("square") layers index
blocks by the client's hidden ids, the anchored embedding and head by
its anchored ids.
"""

from __future__ import annotations

from typing import Dict, List

import numpy as np


def merge(prev: Dict[str, dict], clients: List[Dict[str, dict]],
          hidden_ids: List[np.ndarray], anchored_ids: List[np.ndarray],
          modes: Dict[str, str]) -> Dict[str, dict]:
    out = {}
    for name, mode in modes.items():
        bases = np.stack([np.asarray(c[name]["basis"], np.float64)
                          for c in clients])
        coeff = np.asarray(prev[name]["coeff"], np.float64)
        acc = np.zeros_like(coeff)
        cnt = np.zeros(coeff.shape[0])
        ids_all = hidden_ids if mode == "square" else anchored_ids
        for c, ids in zip(clients, ids_all):
            ids = np.asarray(ids)
            np.add.at(acc, ids, np.asarray(c[name]["coeff"], np.float64))
            np.add.at(cnt, ids, 1.0)
        trained = cnt > 0
        new = coeff.copy()
        new[trained] = acc[trained] / cnt[trained][:, None, None]
        out[name] = {"basis": bases.mean(0), "coeff": new}
    return out
