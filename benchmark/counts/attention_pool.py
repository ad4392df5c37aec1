"""Operations and bytes the forward attention pool needs over the window:
tanh(c W), the masked softmax of its scores, the weighted sum.

Work the algorithm needs, not what an implementation moves: each valid
context is read once in the compute dtype the configuration states, W
and the attention vector once a step in float32, the code vector [D] and
the attention weights written once per method in float32."""

_BYTES = {"bfloat16": 2, "float32": 4}


def work(sizes: dict, window: dict) -> dict:
    d = sizes["code_vector"]
    flops = window["contexts"] * (2 * d * d + 4 * d)
    read = window["contexts"] * d * _BYTES[sizes["compute_dtype"]] \
        + window["steps"] * (d * d + d) * 4
    written = window["methods"] * d * 4 + window["contexts"] * 4
    return {"flops": float(flops), "bytes": float(read + written)}
