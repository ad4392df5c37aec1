"""Operations and bytes the fused multi-head attention needs over the
window, forward and backward kernels together.

Per valid method with n valid contexts, per layer: forward QK^T and PV,
4 n^2 D; backward dV, dP, dQ and dK, 8 n^2 D (the scores the backward
recomputes are not counted). Bytes in the compute dtype: forward reads
q, k, v and writes o (4 n D); backward reads q, k, v, do and writes dq,
dk, dv (7 n D)."""

_BYTES = {"bfloat16": 2, "float32": 4}


def work(sizes: dict, window: dict) -> dict:
    d, layers = sizes["code_vector"], sizes["xf_layers"]
    flops = layers * 12 * d * window["contexts_sq"]
    moved = layers * 11 * d * window["contexts"] \
        * _BYTES[sizes["compute_dtype"]]
    return {"flops": float(flops), "bytes": float(moved)}
