"""The numbers that decide ``correct``, each beside its limit."""
from __future__ import annotations

from typing import Dict, Iterable, List, Optional, Tuple

import numpy as np

# a leaf whose reference gradient is under this share of the median leaf's
# moves under AdamW by round-off alone (see PERF.md): its change is not
# compared
ZERO_GRAD_SHARE = 1e-3


def logit_gaps(ref_rows: np.ndarray, tokens: np.ndarray) -> np.ndarray:
    """For each row, how far the reference's logit of ``tokens`` lies below
    the reference's best logit of that row (0 for the reference's argmax)."""
    ref = np.asarray(ref_rows, np.float64)
    tok = np.asarray(tokens).reshape(-1)
    return ref.max(-1) - ref[np.arange(ref.shape[0]), tok]


def rel_gap(prog: float, ref: float) -> float:
    return abs(prog - ref) / max(abs(ref), 1e-30)


def counted_leaves(ref_grad_norms: Dict[str, float]) -> List[str]:
    """Leaves whose reference gradient is not nought to rounding."""
    med = float(np.median(list(ref_grad_norms.values())))
    return sorted(k for k, v in ref_grad_norms.items()
                  if v >= ZERO_GRAD_SHARE * med)


def worst_leaf_gap(prog: Dict[str, float], ref: Dict[str, float],
                   leaves: Iterable[str]) -> Tuple[float, str]:
    """The gap between the program's and the reference's norm of each leaf,
    over the reference's norm of that leaf or of the median leaf, whichever
    is larger; the worst leaf and its gap."""
    leaves = list(leaves)
    med = float(np.median([ref[k] for k in leaves]))
    gaps = {k: abs(prog[k] - ref[k]) / max(ref[k], med, 1e-30)
            for k in leaves}
    worst = max(gaps, key=gaps.get)
    return gaps[worst], worst


def verdict(numbers: Dict[str, Optional[float]],
            limits: Dict[str, float]) -> Tuple[bool, Dict[str, Dict]]:
    """``correct`` and the checks line: every limited number must be present,
    finite and at most its limit."""
    checks = {}
    ok = True
    for name, limit in limits.items():
        v = numbers.get(name)
        good = v is not None and bool(np.isfinite(v)) and v <= limit
        ok &= good
        checks[name] = {"value": None if v is None else float(v),
                        "limit": float(limit)}
    return ok, checks
