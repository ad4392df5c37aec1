"""Operations and bytes the grouped product over the held experts needs
over the window, forward and backward together.

Per routed row, forward: h W1_e, h W3_e and (.) W2_e, 6 H F; backward
twice that (the input's gradient and the weight's). Bytes, in the compute
dtype, a pass (forward, input gradient, weight gradient) and an expert
layer: the held experts' weights once (3 H F each) and each routed row in
and out (2 H). The recomputed forward is not counted."""

_BYTES = {"bfloat16": 2, "float32": 4}


def work(sizes: dict, window: dict) -> dict:
    h, f = sizes["hidden_size"], sizes["moe_intermediate_size"]
    layers = sum(i >= sizes["num_dense_layers"]
                 for i in range(len(sizes["layer_types"])))
    rows = window["routed_rows"]
    weights = window["steps"] * layers * sizes["num_experts"] * 3 * h * f
    moved = 3 * (weights + rows * 2 * h) * _BYTES[sizes["compute_dtype"]]
    return {"flops": float(18 * h * f * rows), "bytes": float(moved)}
