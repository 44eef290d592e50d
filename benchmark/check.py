"""The comparison that decides ``correct``: what the timed path produced
against the plain reference, each number against a limit of its own.

The limits live in ``benchmark/limits/<workload>.json``, each beside the two
readings it was set from (``lower``: the largest that sound runs of the
program gave; ``upper``: the smallest that the control or a fault gave). A
number with no entry there is printed and not compared.
"""

from __future__ import annotations

import statistics

import numpy as np

# A leaf whose gradient is nought to rounding in the reference moves under
# Adam by round-off alone: left out of the change by this rule, not by name.
NOUGHT_GRADIENT_SHARE = 1e-3


def worst_leaf_gap(program: dict, reference: dict, skip=()) -> tuple[float, str]:
    """The worst leaf's gap between the program's norm and the reference's
    (not the norm of their difference), measured against the reference's
    norm of that leaf or of the median leaf, whichever is larger."""
    median = statistics.median(reference.values())
    worst, where = 0.0, ""
    for name, ref in reference.items():
        if name in skip:
            continue
        gap = abs(program[name] - ref) / max(ref, median)
        if gap > worst or not where:
            worst, where = gap, name
    return worst, where


def worst_leaf_difference(program_tree, reference_tree, reference: dict,
                          leaf_norms):
    """The worst leaf's norm of the difference between the two trees - what
    rounding noise shows in, where a gap of norms averages it away -
    against the reference's norm of that leaf or of the median leaf.
    ``leaf_norms`` is the family's: it says what a leaf is."""
    import jax

    diff = leaf_norms(jax.tree_util.tree_map(
        lambda a, b: a - b, program_tree, reference_tree))
    median = statistics.median(reference.values())
    name = max(diff, key=lambda n: diff[n] / max(reference[n], median))
    return diff[name] / max(reference[name], median), name


def nought_gradient_leaves(reference_grad: dict) -> set[str]:
    median = statistics.median(reference_grad.values())
    return {n for n, v in reference_grad.items()
            if v < NOUGHT_GRADIENT_SHARE * median}


def training_numbers(program: dict, reference: dict, leaf_norms) -> dict:
    """``program`` and ``reference`` each hold ``losses`` (one a step),
    ``grad`` (leaf norms of the first gradient as the optimizer got it) and
    ``moved`` (leaf norms of the parameters' change over those steps); the
    reference also counts the rows that the program was fed wrong.
    ``leaf_norms`` is the family's view of a parameter tree."""
    numbers = {}
    if "data_rows_wrong" in reference:
        numbers["data_rows_wrong"] = reference["data_rows_wrong"]
    for i, (lp, lr) in enumerate(zip(program["losses"], reference["losses"]), 1):
        numbers[f"loss_gap_step{i}"] = abs(lp - lr)
    numbers["grad_norm_gap"], grad_leaf = worst_leaf_gap(
        program["grad"], reference["grad"])
    numbers["moved_norm_gap"], moved_leaf = worst_leaf_gap(
        program["moved"], reference["moved"],
        skip=nought_gradient_leaves(reference["grad"]))
    leaves = {"grad_norm_gap": grad_leaf, "moved_norm_gap": moved_leaf}
    if "grad_tree" in program and "grad_tree" in reference:
        numbers["grad_diff"], leaves["grad_diff"] = worst_leaf_difference(
            program["grad_tree"], reference["grad_tree"], reference["grad"],
            leaf_norms)
    return {"numbers": numbers, "leaves": leaves}


def token_logit_gaps(ref_logits: np.ndarray, prompt_len: int,
                     tokens: list[int]) -> np.ndarray:
    """For each token that followed position ``prompt_len - 1 + i``, the gap
    by which its logit lies below the reference's best at that position.
    ``ref_logits`` is the reference's [T, V] over prompt + tokens."""
    pos = np.arange(prompt_len - 1, prompt_len - 1 + len(tokens))
    rows = ref_logits[pos]
    return rows.max(axis=-1) - rows[np.arange(len(tokens)), np.asarray(tokens)]


def judge(numbers: dict, limits: dict) -> tuple[bool, list[dict]]:
    """Each number beside its limit. ``correct`` needs every compared
    number finite and at or under its limit, and at least one compared."""
    rows, correct, compared = [], True, 0
    for name, value in numbers.items():
        limit = limits.get(name, {}).get("limit")
        ok = None
        if limit is not None:
            compared += 1
            ok = bool(np.isfinite(value) and value <= limit)
            correct = correct and ok
        rows.append({"name": name, "value": float(value), "limit": limit, "ok": ok})
    return correct and compared > 0, rows
