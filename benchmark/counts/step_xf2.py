"""Operations one training step of the transformer encoder needs, forward
and backward, for the valid methods and valid contexts the window trained.

Per valid context (D = 3E, r = MLP ratio, L layers): input projection
2 D^2; per layer QKV 6 D^2, output projection 2 D^2, MLP 4 r D^2; the
pool's score and weighted sum 4 D. Per valid method with n valid
contexts, per layer: scores and weighted values over its own n keys,
4 n^2 D. Per valid method: sampled-softmax logits 2 D (S + 1). Backward
costs twice the forward; a rematerialised or recomputed forward is not
counted."""


def flops(sizes: dict, window: dict) -> float:
    d, layers = sizes["code_vector"], sizes["xf_layers"]
    per_context = 2 * d * d + layers * (8 + 4 * sizes["xf_mlp_ratio"]) \
        * d * d + 4 * d
    attention = layers * 4 * d * window["contexts_sq"]
    per_method = 2 * d * (sizes["num_sampled"] + 1)
    forward = (window["contexts"] * per_context + attention
               + window["methods"] * per_method)
    return 3.0 * forward
