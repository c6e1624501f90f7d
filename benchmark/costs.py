"""Operations and bytes a training step needs, from shapes alone.

These are the yardstick's numerators: ``step_roofline_share`` divides the
least time they allow on the device (``roofline_seconds``) by the device
time the trace shows.  Nothing here comes from XLA's cost analysis, which
counts what a compiled program happens to move (29.2 GB for a step whose
operands are ~100 MB, PERF.md).  Everything is float32 (4 bytes); a
training step is counted as three times its forward matmul work (forward,
gradient by inputs, gradient by weights).

Each function returns ``{"flops": f, "bytes": b}`` for ONE step.
"""

from __future__ import annotations

import json
import os

F32 = 4


def sparse_step(distinct_keys: float, row_width: int) -> dict:
    """Pull and push of the step's distinct keys, as planned: every
    distinct row [show, click, embed...] and its g2sum is read once and
    written once.  The adagrad arithmetic (~8 flops per element) is
    counted; duplicates' merge is not (it is on-chip work at best)."""
    per_row = (row_width + 1) * F32
    return {"flops": 8.0 * distinct_keys * row_width,
            "bytes": 2.0 * distinct_keys * per_row}


def mlp_train(batch: int, dims: list) -> dict:
    """A chain of linear layers dims[0] -> ... -> dims[-1], trained with
    Adam: matmul flops three times forward; weights read forward and
    backward, gradient written, Adam reading and writing p, m, v;
    activations written forward and read backward."""
    flops = by = 0.0
    for i, o in zip(dims[:-1], dims[1:]):
        n_w = i * o + o
        flops += 3 * 2.0 * batch * i * o
        by += n_w * F32 * (2 + 1 + 6)
        by += 2.0 * batch * (i + o) * F32
    return {"flops": flops, "bytes": by}


def cin_layer_train(batch: int, h: int, h_prev: int, m: int, d: int) -> dict:
    """One CIN layer X_k[b,h,d] = sum_ij W[h,i,j] X_{k-1}[b,i,d] X0[b,j,d]:
    the outer product and its compression are 2*B*h*h_prev*m*d flops
    forward.  Bytes: W like any Adam-trained weight, X_{k-1}, X0 and X_k
    written once and read once."""
    n_w = h * h_prev * m
    flops = 3 * 2.0 * batch * h * h_prev * m * d
    by = n_w * F32 * (2 + 1 + 6) + 2.0 * batch * d * (h + h_prev + m) * F32
    return {"flops": flops, "bytes": by}


def total(parts: list) -> dict:
    return {"flops": sum(p["flops"] for p in parts),
            "bytes": sum(p["bytes"] for p in parts)}


def load_peaks(device_kind: str) -> dict:
    """The device's published peaks; a device not in the table is an
    error, never a default."""
    path = os.path.join(os.path.dirname(__file__), "peaks.json")
    with open(path) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(
            f"no peaks for device kind {device_kind!r} in {path}: add it "
            "with its source before reporting a roofline share")
    return table[device_kind]


def roofline_seconds(cost: dict, peaks: dict) -> tuple:
    """(least seconds, which bound) for one step on one chip."""
    t_flops = cost["flops"] / peaks["matmul_flops_per_s"]
    t_bytes = cost["bytes"] / peaks["hbm_bytes_per_s"]
    return (t_flops, "flops") if t_flops >= t_bytes else (t_bytes, "bytes")
