"""A second family, as a later PR would bring it: a dense decoder whose
configuration spells its sizes the other way (`hidden_size`,
`num_attention_heads`, `num_hidden_layers`, `intermediate_size`,
`max_position_embeddings`), with its own tree builder and its own
reference beside it. `test_add_by_files.py` copies this file to
`benchmark/families/hf_dense.py` of a temporary benchmark and edits no
file that was there. It answers what a serving cell asks; it trains
nothing, so it has no `make_train_step`."""

import os

from benchmark import manifest, schedule


def sizes(config):
    return {"vocab_size": int(config["vocab_size"]),
            "max_len": int(config["max_position_embeddings"]),
            "width": int(config["hidden_size"]),
            "heads": int(config["num_attention_heads"]),
            "depth": int(config["num_hidden_layers"]),
            "inner": int(config["intermediate_size"])}


def param_shapes(config):
    s = sizes(config)
    d, f = s["width"], s["inner"]
    norm = {"g": (d,), "b": (d,)}
    blocks = [{"ln1": norm, "Wq": (d, d), "Wk": (d, d), "Wv": (d, d),
               "Wo": (d, d), "ln2": norm, "W1": (d, f), "b1": (f,),
               "W2": (f, d), "b2": (d,)} for _ in range(s["depth"])]
    return {"embed": (s["vocab_size"], d), "pos": (s["max_len"], d),
            "ln_f": norm, "blocks": blocks}


def is_gain(path):
    return path.endswith("['g']")


def build_engine(config, params):
    import jax.numpy as jnp

    from deeplearning4j_tpu.models.transformer import TransformerConfig
    from deeplearning4j_tpu.serving.engine import InferenceEngine

    s, srv = sizes(config), config["serving"]
    cfg = TransformerConfig(
        vocab_size=s["vocab_size"], d_model=s["width"], n_heads=s["heads"],
        n_layers=s["depth"], d_ff=s["inner"], max_len=s["max_len"],
        dtype=jnp.dtype(config["dtype"]))
    return InferenceEngine.for_transformer(
        params, cfg, decode_slots=int(srv["max_num_seqs"]),
        page_size=int(srv["block_size"]),
        kv_pages=int(srv["num_blocks"]))


def reference():
    return manifest.module_at(os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "reference", "hf_dense.py"))


def decode_token_flops(ctx, context):
    s = sizes(ctx["config"])
    d = s["width"]
    body = s["depth"] * (4 * d * d + 2 * d * s["inner"])
    return 2 * (body + s["vocab_size"] * d) \
        + 4 * s["depth"] * d * int(context)


def warm_requests(config, traffic, seconds):
    srv, max_len = config["serving"], sizes(config)["max_len"]
    page = int(srv["block_size"])
    buckets = [b for b in (page << i for i in range(32)) if b < max_len]
    plan = schedule.warm_groups(traffic, seconds,
                                int(srv["max_num_seqs"]),
                                buckets + [max_len])
    return [(n, min(tb, max_len - 2))
            for tb in plan["buckets"] for n in plan["sizes"]]
