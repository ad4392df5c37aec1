"""Operations one training step of the bag encoder needs, forward and
backward, for the valid methods and valid contexts the window trained.

Per valid context (D = 3E): the dense layer c W, 2 D^2; its score
against the attention vector, 2 D; its share of the weighted sum, 2 D.
Per valid method: the logits of 1 true and S sampled classes, 2 D (S + 1).
Embedding lookups, tanh, softmax, dropout and the optimizer move bytes
and count for nothing here. Backward costs twice the forward (one
product for the input's gradient, one for the weight's), so the step is
three times the forward; nothing recomputed is counted."""


def flops(sizes: dict, window: dict) -> float:
    d = sizes["code_vector"]
    per_context = 2 * d * d + 4 * d
    per_method = 2 * d * (sizes["num_sampled"] + 1)
    forward = (window["contexts"] * per_context
               + window["methods"] * per_method)
    return 3.0 * forward
