"""The comparisons that decide `correct`. Each returns one number that is
held against a limit of its own (PERF.md says what each limit was set from)."""
from __future__ import annotations

import statistics


def loss_gap(program, reference) -> float:
    """Worst step: |program's loss - reference's| over the reference's."""
    return max(abs(p - r) / abs(r) for p, r in zip(program, reference))


def norm_gap(program, reference, leave_out=()) -> tuple:
    """Worst leaf: the gap between the program's norm and the reference's
    (not the norm of a difference), against the reference's norm of that leaf
    or of the median leaf, whichever is larger. Returns (gap, leaf index)."""
    floor = statistics.median(reference)
    worst, at = 0.0, -1
    for i, (p, r) in enumerate(zip(program, reference)):
        if i in leave_out:
            continue
        gap = abs(p - r) / max(r, floor)
        if not gap <= worst:            # a nan is the worst there is
            worst, at = gap, i
    return worst, at


def unmoved_leaves(reference_grad_norms) -> set:
    """Leaves whose gradient is nought to rounding in the reference (a key's
    bias under softmax): under a thousandth of the median leaf's norm. Adam
    moves them by round-off alone, so their change is not compared."""
    floor = 1e-3 * statistics.median(reference_grad_norms)
    return {i for i, g in enumerate(reference_grad_norms) if g < floor}


def served_gap(reference_logits, served_tokens) -> float:
    """Widest gap, over the positions, by which the served token's logit lies
    below the reference's best. `reference_logits` [n, V], host array."""
    import numpy as np
    z = np.asarray(reference_logits, np.float64)
    tok = np.asarray(served_tokens, np.int64)
    return float(np.max(z.max(axis=1) - z[np.arange(len(tok)), tok]))


def logit_rel_err(program_logits, reference_logits) -> float:
    """Error of the program's logits against the reference's over all
    positions compared: the norm of the difference over the norm of the
    reference's logits about each position's mean. Lists of [n, V] arrays."""
    import numpy as np
    num = den = 0.0
    for p, r in zip(program_logits, reference_logits):
        p, r = np.asarray(p, np.float64), np.asarray(r, np.float64)
        num += float(np.sum(np.square(p - r)))
        den += float(np.sum(np.square(r - r.mean(axis=1, keepdims=True))))
    return (num / den) ** 0.5 if den else float("nan")


def verdict(checks) -> bool:
    """`checks`: [(name, value, limit)]. A value that is not a number fails;
    a limit of None (a cell file whose limit is not set yet) judges nothing."""
    return all(v == v and (limit is None or v <= limit)
               for _, v, limit in checks)
