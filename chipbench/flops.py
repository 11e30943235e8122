"""Model FLOPs of one training step, counted from shapes.

`compiled.cost_analysis()` is not used: it costs the body of a scanned
layer stack once, whatever the depth. These counts are what the model
needs, not what the program happens to do: recomputation is not counted.
"""
from __future__ import annotations


def dense_lm_matmul_params(c: dict) -> dict:
    """Matrix-multiply parameters of a decoder-only LM with GQA attention
    and a SwiGLU MLP, from the configuration file's keys. The embedding
    lookup is a gather; a tied embedding counts once, as the head."""
    d, L = c["hidden_size"], c["num_hidden_layers"]
    h, kv, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                 c["head_dim"])
    attn = d * h * hd + 2 * d * kv * hd + h * hd * d
    mlp = 3 * d * c["intermediate_size"]
    return {"head": c["vocab_size"] * d, "layer": attn + mlp,
            "layers": L * (attn + mlp)}


def dense_lm_train_flops(c: dict, batch: int, seq: int) -> dict:
    """Forward + backward FLOPs of one step over `batch` x `seq` tokens:
    6 x matmul params x tokens, plus causal attention (scores and values,
    2 x 2 x seq^2 / 2 x heads x head_dim per sequence and layer forward,
    three times that with the backward pass)."""
    p = dense_lm_matmul_params(c)
    tokens = batch * seq
    attn = (c["num_hidden_layers"] * 3 * 4 * batch * seq * seq / 2
            * c["num_attention_heads"] * c["head_dim"])
    parts = {"head": 6.0 * p["head"] * tokens,
             "layer": 6.0 * p["layer"] * tokens,
             "attention": attn}
    parts["total"] = (parts["head"] + c["num_hidden_layers"] * parts["layer"]
                      + attn)
    return parts
