"""Follow a Heroes run's first rounds with the plain reference and
compare what the program produced.

The reference makes the initial factors from the seed itself
(``transformer.init_params``), draws each client's minibatch rows by the
engine's documented host RNG contract (``default_rng((seed, round,
client))``: tau training draws of ``batch`` rows), trains every client
of the round with plain SGD and merges by Eq. 5.  From the program it
takes only the assignments (who trained which width, tau and blocks) and
the data the benchmark generated; it takes no weight the program made.

Three numbers are compared, each against its limit:

``loss``     the widest relative gap of a client's loss at the factors it
             received, over the clients of the rounds followed (the loss
             after local training is not compared: at this learning rate
             a step often raises the loss, and how far swings with the
             rounding of the step, so that reading is noise of the
             later steps and carries no precision signal);
``update1``  the widest gap between the norms of round 1's server
             update (theta1 - theta0) per leaf, program against reference,
             over the larger of the leaf's reference norm and the median
             leaf's;
``change3``  the same for the change over all rounds followed;
``update1_diff``, ``change3_diff``  the widest per-leaf norm of the
             difference of the two changes, over the same floor: a merge
             that writes a block to the wrong slot, or with the wrong
             sign, keeps every leaf's norm and shows only here.

Leaves whose reference change is under a thousandth of the median
leaf's are left out of the norms (coefficient blocks that no client
trained move by nothing, or by round-off).
"""

from __future__ import annotations

from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from . import merge as merge_lib
from . import transformer as ref

SKIP_BELOW = 1e-3


def batch_indices(seed: int, rnd: int, client: int, num_samples: int,
                  tau: int, batch: int) -> np.ndarray:
    rng = np.random.default_rng((seed, rnd, client))
    return np.stack([rng.integers(0, num_samples, batch) for _ in range(tau)])


def _host(tree):
    return jax.tree_util.tree_map(lambda a: np.asarray(a, np.float64),
                                  jax.device_get(tree))


def follow(m: dict, seed: int, lr: float, batch: int, x: np.ndarray,
           y: np.ndarray, parts: List[np.ndarray], rounds: List[dict],
           dtype=jnp.float32, half_batch: bool = False) -> dict:
    """Train and merge ``rounds`` (each ``{"assigns": {client: {width,
    tau, hidden_ids, anchored_ids}}}``, in the program's client order)
    from the seed; returns the client losses in order, which client and
    round each belongs to, and the server factors before the first
    round, after it and after the last.  A ``dtype`` below float32 keeps
    the factors in that type too (initial, trained and merged)."""
    specs = ref.layer_specs(m)
    modes = {n: s[0] for n, s in specs.items()}
    params = jax.tree_util.tree_map(lambda a: a.astype(dtype),
                                    ref.init_params(m, seed))
    theta = {0: _host(params)}
    losses, who = [], []
    for r, rec in enumerate(rounds):
        trained, hid, anc = [], [], []
        for n, a in rec["assigns"].items():
            n = int(n)
            tau = max(int(a["tau"]), 1)
            rows = parts[n][batch_indices(seed, r, n, len(parts[n]), tau,
                                          batch)]
            if half_batch:
                rows = rows[:, : batch // 2]
            batches = [{"tokens": jnp.asarray(x[rw]),
                        "labels": jnp.asarray(y[rw])} for rw in rows]
            red = ref.reduce(params, a["hidden_ids"], a["anchored_ids"],
                             specs)
            red, before, after = ref.local_train(red, int(a["width"]),
                                                 batches, lr, m, dtype)
            losses += [before, after]
            who += [(r + 1, n, int(a["width"]), tau, "before"),
                    (r + 1, n, int(a["width"]), tau, "after")]
            trained.append(_host(red))
            hid.append(np.asarray(a["hidden_ids"]))
            anc.append(np.asarray(a["anchored_ids"]))
        merged = merge_lib.merge(_host(params), trained, hid, anc, modes)
        params = jax.tree_util.tree_map(lambda a: jnp.asarray(a, dtype),
                                        merged)
        if r == 0 or r == len(rounds) - 1:
            theta[r + 1] = merged
    return {"losses": np.asarray(losses), "who": who, "theta": theta}


def _changes(after, before) -> Dict[str, np.ndarray]:
    return {f"{name}.{k}": np.asarray(after[name][k], np.float64)
            - np.asarray(before[name][k], np.float64)
            for name in before for k in ("basis", "coeff")}


def _widest(prog_after, prog_before, ref_after, ref_before, gap) -> float:
    """Widest ``gap(program change, reference change)`` of a leaf over
    max(reference leaf norm, median leaf norm), over the leaves the
    reference moves."""
    rc = _changes(ref_after, ref_before)
    pc = _changes(prog_after, prog_before)
    rn = {k: float(np.linalg.norm(v)) for k, v in rc.items()}
    med = float(np.median(list(rn.values())))
    return max(gap(pc[k], rc[k]) / max(rn[k], med)
               for k in rn if rn[k] >= SKIP_BELOW * med)


def norm_gap(prog_after, prog_before, ref_after, ref_before) -> float:
    """Widest per-leaf gap between the norms of the program's and the
    reference's change."""
    return _widest(prog_after, prog_before, ref_after, ref_before,
                   lambda p, r: abs(np.linalg.norm(p) - np.linalg.norm(r)))


def diff_gap(prog_after, prog_before, ref_after, ref_before) -> float:
    """Widest per-leaf norm of the difference of the two changes."""
    return _widest(prog_after, prog_before, ref_after, ref_before,
                   lambda p, r: np.linalg.norm(p - r))


def readings(prog: dict, refd: dict) -> Dict[str, float]:
    """The compared numbers of a program run (``losses`` in the same
    order, ``theta`` at 0, 1 and the last round) against the reference."""
    last = max(refd["theta"])
    # losses come in (before, after) pairs; compare the befores
    lp, lr = np.asarray(prog["losses"])[::2], refd["losses"][::2]
    out = {"loss": float(np.max(np.abs(lp - lr) / np.abs(lr)))}
    for name, r in (("update1", 1), ("change3", last)):
        pair = (prog["theta"][r], prog["theta"][0],
                refd["theta"][r], refd["theta"][0])
        out[name] = norm_gap(*pair)
        out[name + "_diff"] = diff_gap(*pair)
    return out
