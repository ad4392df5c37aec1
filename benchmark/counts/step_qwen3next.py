"""Operations one training step of the Qwen3-Next encoder needs, forward
and backward, for what the window trained: valid positions only (a PAD
slot needs nothing), the experts by the rows really routed to those held
here, the delta rule by `gdn_scan.py`'s operations.

H hidden, D = 3E, n heads of hd with n_kv key/value heads, n_k key and n_v
value heads of d_k and d_v in a linear layer (K = n_k d_k, V = n_v d_v),
T conv taps, E routed experts of width F, a shared expert of width Fs. Per
valid position: input projection 2 D H; the pool's score and weighted sum
4 H; a linear layer's projections 2 H (2 K + 2 V) + 2 H 2 n_v, its
convolution 2 T (2 K + V), its rule 6 d_k d_v n_v, its output 2 V H; an
attention layer's q with its gate 2 H 2 n hd, k and v 4 H n_kv hd, o
2 n hd H; every layer's router 2 H E, shared expert 6 H Fs and its gate
2 H. Attention is causal: a method of m valid contexts has m (m + 1) / 2
query-key pairs, each 4 n hd (scores and weighted values over all heads).
Per routed row: 6 H F. Per valid method: output projection 2 H D,
sampled-softmax logits 2 D (S + 1). Backward costs twice the forward; the
rematerialised forward and chunking's own work are not counted."""


def flops(sizes: dict, window: dict) -> float:
    h, d = sizes["hidden_size"], sizes["code_vector"]
    n, hd = sizes["num_attention_heads"], sizes["head_dim"]
    kv = sizes["num_key_value_heads"] * hd
    n_v = sizes["linear_num_value_heads"]
    d_k, d_v = sizes["linear_key_head_dim"], sizes["linear_value_head_dim"]
    key, value = sizes["linear_num_key_heads"] * d_k, n_v * d_v
    every_layer = (2 * h * sizes["num_routed_experts"]
                   + 6 * h * sizes["shared_expert_intermediate_size"]
                   + 2 * h)
    per_position = 2 * d * h + 4 * h
    pairs = 0
    for kind in sizes["layer_types"]:
        per_position += every_layer
        if kind == "linear_attention":
            per_position += (2 * h * (2 * key + 2 * value) + 4 * h * n_v
                             + 2 * sizes["linear_conv_kernel_dim"]
                             * (2 * key + value)
                             + 6 * d_k * d_v * n_v + 2 * value * h)
        else:
            per_position += 4 * h * n * hd + 4 * h * kv + 2 * n * hd * h
            pairs += 4 * n * hd
    attention = pairs * (window["contexts_sq"] + window["contexts"]) / 2
    per_row = 6 * h * sizes["moe_intermediate_size"]
    per_method = 2 * h * d + 2 * d * (sizes["num_sampled"] + 1)
    assert "routed_rows" in window, \
        "the window holds no routed rows (no moe/route record)"
    forward = (window["contexts"] * per_position + attention
               + window["routed_rows"] * per_row
               + window["methods"] * per_method)
    return 3.0 * forward
