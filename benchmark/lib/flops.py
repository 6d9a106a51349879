"""Operations the algorithm needs, computed from shapes. Kept with the
benchmark so that no PR that claims a gain can change the arithmetic."""


def bert_mlm_train_flops_per_token(model_kwargs, seq_len):
    """Forward + backward matmul FLOPs of one input token of the BERT MLM
    step AS THIS PROGRAM COMPUTES IT: every position is projected onto the
    vocabulary (the published recipe projects only the ~20 masked positions
    of 128; recorded in PERF.md as a departure). 6 FLOPs per matmul
    parameter per token (2 forward, 4 backward) plus the attention scores
    and weighted sum, 12 * layers * hidden * seq. Embedding lookups, layer
    norms, softmax and the pooler (unused by the MLM loss) are not counted;
    recomputed operations would not count either."""
    h = model_kwargs["hidden_size"]
    layers = model_kwargs["num_hidden_layers"]
    inter = model_kwargs["intermediate_size"]
    vocab = model_kwargs["vocab_size"]
    encoder = layers * (4 * h * h + 2 * h * inter)
    transform = h * h
    tied_decoder = vocab * h
    return (6 * (encoder + transform + tied_decoder)
            + 12 * layers * h * seq_len)


TRAIN_FLOPS_PER_TOKEN = {
    "paddle_tpu.text.models.bert.Bert": bert_mlm_train_flops_per_token,
}


def train_flops_per_token(model_class, model_kwargs, seq_len):
    if model_class not in TRAIN_FLOPS_PER_TOKEN:
        raise KeyError(f"no FLOP function for {model_class!r}; add one to "
                       "benchmark/lib/flops.py")
    return TRAIN_FLOPS_PER_TOKEN[model_class](model_kwargs, seq_len)
