"""The numbers that decide ``correct``, from the program's readings and
the plain reference's.

Training (per cell: ``loss_gap``, ``grad1_gap``, ``change_gap``):
  * ``loss_gap``: the largest |program - reference| loss over the check
    steps, in nats;
  * ``grad1_gap``: over the parameter leaves, the largest gap between the
    program's and the reference's norm of the first clipped gradient,
    over the larger of the reference's norm of that leaf and of the median
    leaf;
  * ``change_gap``: the same of the parameters' change after the check
    steps;
  * ``grad1_gap_mean``: the mean gap of the first clipped gradient over
    the leaves, the worst left out (``grad1_gap`` holds it): steadier from
    seed to seed than the worst leaf or the median leaf, whose gaps swing
    with one small leaf's noise;
  * ``change_gap_median``: the median leaf's gap of the change.
  A cell compares the numbers its limits file names.
  Leaves whose reference gradient is under a thousandth of the median
  leaf's move under Adam by round-off alone and are not counted.

Serving (``token_gap``): the widest gap by which a served token's float32
reference logit lies below the reference's best at its position.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

ROUNDOFF_LEAF = 1e-3


def leaf_norms(tree):
    """Per-leaf float32 norms as one vector, in ``jax.tree.leaves`` order."""
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in jax.tree.leaves(tree)])


def counted_leaves(ref_grad1) -> np.ndarray:
    g = np.asarray(ref_grad1, np.float64)
    return g >= ROUNDOFF_LEAF * np.median(g)


def leaf_gaps(prog, ref, counted) -> np.ndarray:
    prog, ref = np.asarray(prog, np.float64), np.asarray(ref, np.float64)
    scale = np.maximum(ref, np.median(ref[counted]))
    return np.where(counted, np.abs(prog - ref) / scale, 0.0)


def train_numbers(prog: dict, ref: dict) -> dict:
    counted = counted_leaves(ref["grad1"])
    g = leaf_gaps(prog["grad1"], ref["grad1"], counted)[counted]
    c = leaf_gaps(prog["change"], ref["change"], counted)[counted]
    return {
        "loss_gap": float(np.max(np.abs(np.subtract(prog["loss"],
                                                    ref["loss"])))),
        "grad1_gap": float(g.max()),
        "change_gap": float(c.max()),
        "grad1_gap_mean": float(np.mean(np.sort(g)[:-1])),
        "change_gap_median": float(np.median(c)),
    }


def train_detail(prog: dict, ref: dict) -> dict:
    """Where each gap sits, for the run's details line."""
    counted = counted_leaves(ref["grad1"])
    g = leaf_gaps(prog["grad1"], ref["grad1"], counted)
    c = leaf_gaps(prog["change"], ref["change"], counted)
    return {"loss_prog": list(prog["loss"]), "loss_ref": list(ref["loss"]),
            "leaves": int(len(counted)), "counted": int(counted.sum()),
            "grad1_worst_leaf": int(g.argmax()),
            "change_worst_leaf": int(c.argmax()),
            "grad1_leaf_gaps": [float(x) for x in g],
            "change_leaf_gaps": [float(x) for x in c]}


def serve_numbers(gaps) -> dict:
    return {"token_gap": float(max(np.max(g) for g in gaps))}
