"""Operations one training step of the LFM2-MoE encoder needs, forward and
backward, for what the window trained: valid positions only (a PAD slot
needs nothing), the experts by the rows really routed to those held here.

H hidden, D = 3E, I dense width, F expert width, L conv taps, E routed
experts. Per valid position: input projection 2 D H; a conv layer 6 H^2
(in) + 2 L H (taps) + 2 H^2 (out); an attention layer q and o 4 H^2, k and
v 4 H kv; the dense MLP 6 H I; an expert layer's router 2 H E; the pool's
score and weighted sum 4 H. Attention is causal: a method of n valid
contexts has n (n + 1) / 2 query-key pairs, each 4 H (scores and weighted
values over all heads). Per routed row: 6 H F. Per valid method: output
projection 2 H D, sampled-softmax logits 2 D (S + 1). Backward costs twice
the forward; the rematerialised forward is not counted."""


def flops(sizes: dict, window: dict) -> float:
    h, d = sizes["hidden_size"], sizes["code_vector"]
    kv = sizes["num_key_value_heads"] * (h // sizes["num_attention_heads"])
    per_position = 2 * d * h + 4 * h
    pairs = 0
    expert_layers = 0
    for i, kind in enumerate(sizes["layer_types"]):
        if kind == "conv":
            per_position += 8 * h * h + 2 * sizes["conv_L_cache"] * h
        else:
            per_position += 4 * h * h + 4 * h * kv
            pairs += 4 * h
        if i < sizes["num_dense_layers"]:
            per_position += 6 * h * sizes["intermediate_size"]
        else:
            per_position += 2 * h * sizes["num_routed_experts"]
            expert_layers += 1
    attention = pairs * (window["contexts_sq"] + window["contexts"]) / 2
    per_row = 6 * h * sizes["moe_intermediate_size"]
    per_method = 2 * h * d + 2 * d * (sizes["num_sampled"] + 1)
    assert expert_layers == 0 or "routed_rows" in window, \
        "the window holds no routed rows (no moe/route record)"
    forward = (window["contexts"] * per_position + attention
               + window.get("routed_rows", 0) * per_row
               + window["methods"] * per_method)
    return 3.0 * forward
