"""Peaks of the chips this benchmark knows, and the functions that count what
a piece of work costs. One function per thing counted; none of them looks at
what the program does, only at shapes and the configuration.

Peaks: Google Cloud documentation, "TPU v5e" (system architecture page):
197 TFLOP/s bf16, 394 TOP/s int8, 16 GB HBM2e at 819 GB/s per chip. jax
reports that chip as device_kind "TPU v5 lite". A kind that is not in the
table is an error, never a default.
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 394e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no peaks for device kind {device_kind!r}: add a row to "
            "benchmarks/roofline.py PEAKS with its source"
        ) from None


def pad_to(n: int, quantum: int) -> int:
    return -(-n // quantum) * quantum


def roofline_seconds(flops: float, nbytes: float, peak: dict,
                     flops_key: str = "bf16_flops") -> tuple[float, str]:
    """The least time the chip could take, and which roof sets it."""
    t_compute = flops / peak[flops_key]
    t_memory = nbytes / peak["hbm_bytes_per_s"]
    if t_compute >= t_memory:
        return t_compute, "compute"
    return t_memory, "memory"


# -- implicit ALS ------------------------------------------------------------


def als_needed_flops(n_users: int, n_items: int, nnz: int, rank: int,
                     iterations: int, cg_iterations: int) -> float:
    """Operations implicit ALS NEEDS for a whole train, from the
    configuration alone: no padding, no zeros of R. Per iteration and side:
    the edge pass builds b (K columns) and the K x K correction (K^2) from
    the observed pairs only, 2*nnz*(K+K^2); the shared Gram matrix Y^T Y,
    2*n_fixed*K^2; and per solved row (cg_iterations + 1) matvecs of
    2*K^2 in the conjugate-gradient solve."""
    k, k2 = rank, rank * rank
    edge = 2 * (2.0 * nnz * (k + k2))
    gram = 2.0 * (n_users + n_items) * k2
    cg = (n_users + n_items) * (cg_iterations + 1) * 2.0 * k2
    return iterations * (edge + gram + cg)


def dense_half_step_cost(n_users_p: int, n_items_p: int, rank: int,
                         cell_bytes: int) -> tuple[float, float]:
    """(operations, bytes) that ONE dense half-step must spend on the padded
    matrix: R times K + K^2 columns, and R read ONCE (the XLA form reads it
    twice, the Pallas form once; the count is the same for both, so a form
    that reads twice shows as a lower share)."""
    cells = float(n_users_p) * float(n_items_p)
    flops = 2.0 * cells * (rank + rank * rank)
    # the factor operand and the f32 outputs are noise beside R, but they
    # are bytes the pass cannot avoid
    out_rows = max(n_users_p, n_items_p)
    nbytes = cells * cell_bytes + 4.0 * out_rows * (rank + rank * rank) * 2
    return flops, nbytes


# -- serving -----------------------------------------------------------------


def serve_needed_flops(live_queries: int, rank: int, n_items: int) -> float:
    """An exact top-k has to score every item for every live query."""
    return 2.0 * live_queries * rank * n_items


def fused_recommend_cost(n_items_p: int, rank: int, batch_rows: int,
                         live_queries: int, itemsize: int) -> tuple[float, float]:
    """(operations, bytes) of one fused score + top-k pass: the item table
    is streamed once whatever the batch; the query block and the k results
    are noise but counted."""
    flops = 2.0 * live_queries * rank * n_items_p
    nbytes = float(n_items_p) * rank * itemsize + 4.0 * batch_rows * rank
    return flops, nbytes
