"""The comparison that decides `correct` for a training cell.

Both sides give the same readings of the first `check_steps` optimizer
steps: every step's loss, the norm of each leaf of the first gradient (for
the program: as the optimizer got it, worked out from its state after one
step) and the norm of each leaf's change over those steps.  Numbers:

* `loss_gap_<n>`: |program - reference| / |reference| of step n's loss;
* `grad_norm_gap`, `param_change_gap`: by the worst leaf, the gap between
  the program's norm and the reference's (not the norm of a difference),
  against the reference's norm of that leaf or of the median leaf,
  whichever is larger.  Leaves whose reference gradient is under a
  thousandth of the median leaf's (a key projection's bias under softmax)
  move under Adam by round-off alone and are left out of the change.

* `grad_diff_gap`: the norm of the difference between the program's first
  gradient and the reference's, over the reference's norm, all leaves
  together.  Rounding that is not biased leaves a norm where it was (noise
  adds in quadrature), so the gaps of norms above do not tell bfloat16 from
  fp8; the difference does (PERF.md, "How correct is decided").
* `grad_diff_ratio`: `grad_diff_gap` in units of what the reference itself
  reads on the same seed with the operands of its matrix products rounded
  to the precision the configuration states (`reference_yardstick`): how
  hard a seed's gradient is to compute moves both alike, so the ratio is
  steadier from seed to seed than the gap.

Each number has a limit of its own in the cell's `limits/<cell>.json`.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

DEAD_GRADIENT = 1e-3


@jax.jit
def leaf_norms(tree):
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in jax.tree_util.tree_leaves(tree)])


@jax.jit
def _diff_rel(a, b):
    sq = lambda t: sum(jnp.sum(jnp.square(x.astype(jnp.float32)))  # noqa: E731
                       for x in jax.tree_util.tree_leaves(t))
    diff = jax.tree_util.tree_map(lambda x, y: x - y, a, b)
    return jnp.sqrt(sq(diff) / sq(b))


def diff_rel(tree, reference_tree) -> float:
    """||tree - reference|| / ||reference|| over all leaves (either may be a
    host tree; leaves are matched in order)."""
    b = jax.tree_util.tree_leaves(reference_tree)
    a = [jax.device_put(x, y.sharding)
         for x, y in zip(jax.tree_util.tree_leaves(tree), b)]
    return float(_diff_rel(a, b))


def alive_leaves(reference_grad_norms):
    """Leaves whose reference gradient is not nought to rounding."""
    ref_g = np.asarray(reference_grad_norms, float)
    return ref_g >= DEAD_GRADIENT * np.median(ref_g)


def leaf_names(tree) -> list:
    return [jax.tree_util.keystr(path) for path, _ in
            jax.tree_util.tree_flatten_with_path(tree)[0]]


def leaf_gaps(program, reference, keep=None):
    """Every leaf's gap between the two norms, against the reference's norm
    of that leaf or of the median leaf, whichever is larger."""
    program, reference = np.asarray(program, float), np.asarray(reference, float)
    gaps = np.abs(program - reference) / np.maximum(reference,
                                                    np.median(reference))
    if keep is not None:
        gaps = np.where(keep, gaps, 0.0)
    return np.where(np.isfinite(gaps), gaps, np.inf)


def worst_gap(program, reference, keep=None):
    """(gap, index) of the worst leaf."""
    gaps = leaf_gaps(program, reference, keep)
    i = int(np.argmax(gaps))
    return float(gaps[i]), i


def numbers(program: dict, reference: dict, names=None) -> dict:
    """{number: value} and, for the two norms, which leaf was worst."""
    out, worst = {}, {}
    for n, (a, b) in enumerate(zip(program["losses"], reference["losses"]), 1):
        gap = abs(a - b) / abs(b)
        out[f"loss_gap_{n}"] = gap if np.isfinite(gap) else float("inf")
    ref_g = np.asarray(reference["grad_norms"], float)
    out["grad_norm_gap"], i = worst_gap(program["grad_norms"], ref_g)
    worst["grad_norm_gap"] = names[i] if names else i
    out["param_change_gap"], i = worst_gap(
        program["change_norms"], reference["change_norms"],
        keep=alive_leaves(ref_g))
    worst["param_change_gap"] = names[i] if names else i
    if "first_grad" in program and "first_grad" in reference:
        gap = diff_rel(program["first_grad"], reference["first_grad"])
        out["grad_diff_gap"] = gap if np.isfinite(gap) else float("inf")
        if reference.get("grad_diff_yardstick"):
            out["grad_diff_ratio"] = \
                out["grad_diff_gap"] / reference["grad_diff_yardstick"]
    return out, worst


def judge(values: dict, limits: dict):
    """({number: {"value", "limit"}} of the numbers compared, {number: value}
    of those the cell's file names as read but not compared: `"limit": null`
    with the reason).  A number that the file does not name is an error:
    nothing is compared, or left out, by guess."""
    compared, only_read = {}, {}
    for name, value in values.items():
        if name not in limits:
            raise KeyError(f"no limit for {name!r} in the cell's limits file")
        if limits[name]["limit"] is None:
            only_read[name] = float(value)
        else:
            compared[name] = {"value": float(value),
                              "limit": float(limits[name]["limit"])}
    return compared, only_read


def correct(judged: dict) -> bool:
    return all(np.isfinite(j["value"]) and j["value"] <= j["limit"]
               for j in judged.values())
