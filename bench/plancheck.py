"""Checks of a call's plan tensors against the configuration, made by
the reference's replay: the reference trains on the rows and weights
the strategy planned, so a wrong draw or weight shows here or nowhere.

Each function returns how many rules the tensors break (0 when sound).
"""
from __future__ import annotations

import numpy as np


def rows(ref, sats: np.ndarray, idx: np.ndarray, seen: dict) -> int:
    """``idx[r]`` are the rows satellite ``sats[r]`` trains on: each
    replica draws ``local_steps * batch_size`` rows of the training set
    that lie in its partition (as the configuration's dataset says), no
    row is drawn by two satellites over an episode (``seen`` carries the
    owners), and no satellite draws more distinct rows than its
    partition holds."""
    sats, idx = np.asarray(sats), np.asarray(idx)
    sizes = ref.satellite_sizes()
    n = ref.n_train
    if (idx.ndim != 2 or idx.shape != (len(sats), ref.steps * ref.bs)
            or sats.min() < 0 or sats.max() >= len(sizes)):
        return 1
    bad = 0
    inside = (idx >= 0) & (idx < n)
    bad += int((~inside.all(axis=1)).sum())
    mine = ref.in_partition(sats, np.where(inside, idx, 0))
    bad += int((~mine.all(axis=1)).sum())
    owner = seen.setdefault("owner", np.full(n, -1, np.int64))
    for s, r in zip(sats, idx):
        r = np.unique(r[(r >= 0) & (r < n)])
        prev = owner[r]
        bad += int(np.any((prev >= 0) & (prev != s)))
        owner[r] = s
    held = np.bincount(owner[owner >= 0], minlength=len(sizes))
    over = set(np.flatnonzero(held > sizes))
    bad += len(over - seen.get("over", set()))
    seen["over"] = seen.get("over", set()) | over
    return bad


def weights(w: np.ndarray, want: np.ndarray | None = None,
            tol: float = 1e-5) -> int:
    """Fold weights: non-negative and summing to 1; equal to ``want``
    where the reference can work them out itself."""
    w = np.asarray(w, np.float64)
    if w.min() < 0 or abs(w.sum() - 1.0) > tol:
        return 1
    if want is not None and not np.allclose(w, want, rtol=tol, atol=0):
        return 1
    return 0
