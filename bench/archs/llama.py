"""The llama decoder as the program serves it: the program's configuration
for a configuration file, and the layout of its weights.

The keys of a configuration file are those of the model's published
``config.json`` (``hidden_size``, ``num_hidden_layers``, ...), so a file
can be held against its source key by key.
"""
from __future__ import annotations

ATTN = ("wq", "wk", "wv", "wo")
FFN = ("w_gate", "w_up", "w_down")


def dims(c: dict) -> dict:
    d, hd = c["hidden_size"], c["head_dim"]
    H, K, f = c["num_attention_heads"], c["num_key_value_heads"], c["intermediate_size"]
    return {
        "wq": (d, H * hd), "wk": (d, K * hd), "wv": (d, K * hd), "wo": (H * hd, d),
        "w_gate": (d, f), "w_up": (d, f), "w_down": (f, d),
    }


def program_config(c: dict):
    """The program's ``ModelConfig`` for configuration file ``c``."""
    from repro.configs.base import ModelConfig

    if c["hidden_act"] != "silu" or not c["tie_word_embeddings"]:
        raise ValueError("the llama layout here is SwiGLU with tied embeddings")
    return ModelConfig(
        name=c["name"],
        family="dense",
        num_layers=c["num_hidden_layers"],
        d_model=c["hidden_size"],
        num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"],
        head_dim=c["head_dim"],
        d_ff=c["intermediate_size"],
        vocab_size=c["vocab_size"],
        tie_embeddings=True,
        rope_theta=float(c["rope_theta"]),
        norm_eps=float(c["rms_norm_eps"]),
        vocab_pad_multiple=c.get("vocab_pad_multiple", 256),
    )


def weight_layout(c: dict) -> dict:
    """``{path: (shape, init)}`` with ``init`` a normal's stddev or "ones".

    Linears draw N(0, 1/fan_in), the embedding N(0, 0.02**2) (the published
    ``initializer_range``); norm scales are ones.  Paths under ``blocks/``
    are per layer: the generator stacks ``num_hidden_layers`` of them.
    """
    d, V = c["hidden_size"], c["vocab_size"]
    out = {"embed": ((V, d), 0.02), "ln_f/scale": ((d,), "ones")}
    for name, (q, p) in dims(c).items():
        group = "attn" if name in ATTN else "ffn"
        out[f"blocks/{group}/{name}/w"] = ((q, p), q ** -0.5)
    out["blocks/ln1/scale"] = ((d,), "ones")
    out["blocks/ln2/scale"] = ((d,), "ones")
    return out


def linear_work(c: dict) -> dict:
    """Multiply-adds per token row: the block linears and the tied head."""
    per_layer = sum(q * p for q, p in dims(c).values())
    return {
        "linears": c["num_hidden_layers"] * per_layer,
        "head": c["vocab_size"] * c["hidden_size"],
    }
