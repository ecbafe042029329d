"""Operations and bytes the algorithm needs, from shapes alone.

A model's FLOPs per sample come from its payload
(``payloads/<model.kind>.py``: ``forward_flops`` and ``train_flops``);
what a fold moves is the same for every model.
"""
from __future__ import annotations

import parts


def fold_bytes(rows: int, n_params: int, itemsize: int = 4) -> int:
    """One weighted fold of ``rows`` replicas on one chip: read every
    replica and the weights once, write the folded model once. A padded
    copy of the stack that an implementation makes is not counted."""
    return rows * n_params * itemsize + n_params * itemsize + rows * itemsize


def window_flops(model: dict, sim: dict, work: dict) -> float:
    """Model FLOPs of the work a window did: each replica that really
    trained ran ``local_steps`` batches, each eval one forward pass over
    the eval set."""
    payload = parts.payload(model["kind"])
    per_replica = (sim["local_steps"] * sim["batch_size"]
                   * payload.train_flops(model))
    return (work["trained"] * per_replica
            + work["evals"] * sim["eval_samples"]
            * payload.forward_flops(model))
