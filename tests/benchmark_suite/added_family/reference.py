"""The second family's plain reference, with the signature every
reference has: the configuration first, never single sizes. Its block
is the GPT-2 block, so it hands the mathematics to that reference under
the key that one reads."""

from benchmark.reference import gpt2


def logits(config, params, tokens, first, last, mode="f32"):
    return gpt2.logits({"n_head": config["num_attention_heads"]}, params,
                       tokens, first, last, mode=mode)
