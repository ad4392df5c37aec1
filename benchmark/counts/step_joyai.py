"""Operations one training step of the JoyAI-LLM-Flash encoder needs,
forward and backward, for what the window trained: valid positions only (a
PAD slot needs nothing), the experts by the rows really routed to those held
here.

H hidden, D = 3E, n heads; latent attention's ranks r_q and r_kv, a head's
nope + rope = qk for the scores and v for the values; I the dense width, E
routed experts of width F, a shared expert of width Fs = n_shared F. Per
valid position: input projection 2 D H; the pool's score and weighted sum
4 H; every layer's five latent-attention products, W_qa 2 H r_q, W_qb
2 r_q n qk, W_kva 2 H (r_kv + rope), W_kvb 2 r_kv n (nope + v), W_o 2 n v H;
a dense layer's MLP 6 H I; an expert layer's router 2 H E and shared expert
6 H Fs. Attention is causal: a method of m valid contexts has m (m + 1) / 2
query-key pairs, each 2 (qk + v) a head and layer (its score over qk, its
weighted value over v). Per routed row: 6 H F. Per valid method: output
projection 2 H D, sampled-softmax logits 2 D (S + 1). Backward costs twice
the forward; the rematerialised forward is not counted."""


def flops(sizes: dict, window: dict) -> float:
    h, d = sizes["hidden_size"], sizes["code_vector"]
    n = sizes["num_attention_heads"]
    r_q, r_kv = sizes["q_lora_rank"], sizes["kv_lora_rank"]
    nope, rope = sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"]
    v = sizes["v_head_dim"]
    f = sizes["moe_intermediate_size"]
    mixer = (2 * h * r_q + 2 * r_q * n * (nope + rope)
             + 2 * h * (r_kv + rope) + 2 * r_kv * n * (nope + v)
             + 2 * n * v * h)
    per_position = 2 * d * h + 4 * h
    pairs = 0
    for i, _kind in enumerate(sizes["layer_types"]):
        per_position += mixer
        pairs += 2 * (nope + rope + v) * n
        if i < sizes["num_dense_layers"]:
            per_position += 6 * h * sizes["intermediate_size"]
        else:
            per_position += (2 * h * sizes["num_routed_experts"]
                             + 6 * h * sizes["n_shared_experts"] * f)
    attention = pairs * (window["contexts_sq"] + window["contexts"]) / 2
    per_method = 2 * h * d + 2 * d * (sizes["num_sampled"] + 1)
    assert "routed_rows" in window, \
        "the window holds no routed rows (no moe/route record)"
    forward = (window["contexts"] * per_position + attention
               + window["routed_rows"] * 6 * h * f
               + window["methods"] * per_method)
    return 3.0 * forward
