"""Operations and bytes the algorithm needs, from shapes alone.

Model FLOPs count multiply and add as two operations, for the
convolutions and the dense layers (biases, activations and pooling are
left out: under 1% of the total). A training sample costs three forward
passes: the forward, and the backward's two products per layer.
"""
from __future__ import annotations


def _taps(size: int, k: int, valid_only: bool) -> int:
    """Kernel taps summed over one axis of a "SAME" convolution's
    outputs; ``valid_only`` leaves out taps that fall on the padding."""
    if not valid_only:
        return size * k
    half = k // 2
    return sum(min(size, i + half + 1) - max(0, i - half)
               for i in range(size))


def cnn_layer_flops(m: dict, valid_only: bool = False) -> dict:
    """Forward FLOPs per sample of each layer of the paper CNN ("SAME"
    5x5 convolutions, 2x2 max pools). A convolution counts every tap of
    its kernel at every output, as the model's FLOPs are counted;
    ``valid_only`` counts only taps on the image, as XLA's
    ``cost_analysis`` does."""
    c1, c2 = m["channels"]
    k, s, hid, ncls = m["kernel"], m["image_size"], m["hidden"], \
        m["num_classes"]
    flat = (s // 4) ** 2 * c2
    t1, t2 = _taps(s, k, valid_only), _taps(s // 2, k, valid_only)
    return {"conv1": 2 * t1 * t1 * c1 * 1,
            "conv2": 2 * t2 * t2 * c2 * c1,
            "fc1": 2 * flat * hid,
            "fc2": 2 * hid * ncls}


def cnn_params(m: dict) -> int:
    c1, c2 = m["channels"]
    k, s, hid, ncls = m["kernel"], m["image_size"], m["hidden"], \
        m["num_classes"]
    flat = (s // 4) ** 2 * c2
    return (k * k * c1 + c1 + k * k * c1 * c2 + c2 + flat * hid + hid
            + hid * ncls + ncls)


def forward_flops(m: dict) -> int:
    return sum(cnn_layer_flops(m).values())


def train_flops(m: dict) -> int:
    return 3 * forward_flops(m)


def fold_bytes(rows: int, n_params: int, itemsize: int = 4) -> int:
    """One weighted fold of ``rows`` replicas on one chip: read every
    replica and the weights once, write the folded model once. A padded
    copy of the stack that an implementation makes is not counted."""
    return rows * n_params * itemsize + n_params * itemsize + rows * itemsize


def window_flops(model: dict, sim: dict, work: dict) -> float:
    """Model FLOPs of the work a window did: each replica that really
    trained ran ``local_steps`` batches, each eval one forward pass over
    the eval set."""
    per_replica = sim["local_steps"] * sim["batch_size"] * train_flops(model)
    return (work["trained"] * per_replica
            + work["evals"] * sim["eval_samples"] * forward_flops(model))
