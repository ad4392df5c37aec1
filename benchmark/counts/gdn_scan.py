"""Operations and bytes the gated delta rule needs over the window,
forward and backward together, for what the window trained: valid slots
only, the linear-attention layers, every value head.

Per valid slot, layer and value head, forward: the prediction S^T k, the
write k u^T and the read-out S^T q, 2 d_k d_v each: 6 d_k d_v; backward
twice that. Bytes, in the compute dtype, a pass (forward, and two for the
backward) and a layer: per slot q and k in (n_k d_k each), v in and o out
(n_v d_v each), g and beta in (n_v each); the state never leaves the chip.
Chunking's own work (the triangle's inverse, the products within a chunk),
the decay of the state and the recomputed forward are not counted: they
are how, not what."""

_BYTES = {"bfloat16": 2, "float32": 4}


def work(sizes: dict, window: dict) -> dict:
    n_k, n_v = sizes["linear_num_key_heads"], sizes["linear_num_value_heads"]
    d_k, d_v = sizes["linear_key_head_dim"], sizes["linear_value_head_dim"]
    layers = sum(kind == "linear_attention" for kind in sizes["layer_types"])
    slots = window["contexts"] * layers
    a_slot = 2 * n_k * d_k + 2 * n_v * d_v + 2 * n_v
    return {"flops": float(18 * d_k * d_v * n_v * slots),
            "bytes": float(3 * a_slot * slots
                           * _BYTES[sizes["compute_dtype"]])}
