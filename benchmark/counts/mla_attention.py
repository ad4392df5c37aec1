"""Operations and bytes the core of multi-head latent attention needs over
the window, forward and backward together, for what the window trained:
valid slots only, every layer, every head. The core is the scores, the
softmax and the weighted values; the five projections around it are not
counted here.

Operations. A causal query-key pair costs a head 2 qk for its score (qk =
qk_nope_head_dim + qk_rope_head_dim = 192) and 2 v for its weighted value
(v = v_head_dim = 128): 2 (192 + 128) = 640 forward, twice that backward,
1,920 in all, over n = 32 heads and the layers. A method of m valid
contexts has m (m + 1) / 2 such pairs, so the window has (`contexts_sq` +
`contexts`) / 2 of them. The softmax's own arithmetic and the recomputed
forward are not counted.

Bytes, in the compute dtype, a pass (forward, and two for the backward), a
valid slot and a layer: q in, n x 192; k_nope in, n x 128; k_rope in, 64
ONCE (one head, every query head's); v in, n x 128; o out, n x 128. The
scores never have to leave the chip."""

_BYTES = {"bfloat16": 2, "float32": 4}


def work(sizes: dict, window: dict) -> dict:
    n, layers = sizes["num_attention_heads"], len(sizes["layer_types"])
    nope, rope = sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"]
    v = sizes["v_head_dim"]
    pairs = (window["contexts_sq"] + window["contexts"]) / 2
    a_slot = n * (nope + rope) + n * nope + rope + n * v + n * v
    return {"flops": float(3 * 2 * (nope + rope + v) * n * layers * pairs),
            "bytes": float(3 * a_slot * layers * window["contexts"]
                           * _BYTES[sizes["compute_dtype"]])}
