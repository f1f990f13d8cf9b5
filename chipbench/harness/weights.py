"""Weights made from the run's seed, on the device, in one jitted call.

The benchmark owns the weights: it makes them in its own layout (one array
per kind, layers stacked on the first axis), hands the program a tree that
points at the same buffers in the program's layout, and gives the plain
reference the same arrays in its own layout.  Nothing the program computes
reaches the reference.

Sizes come from the configuration file's ``model`` block.  Projections are
bf16, the type they are served in; norm gains and qkv biases are f32, as
the program keeps them.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def shapes(m: dict) -> dict:
    """``{name: (shape, dtype, std)}`` of every weight; a norm gain is
    stored as ``g`` and applied as ``1 + g``."""
    L, D, F = m["num_hidden_layers"], m["hidden_size"], m["intermediate_size"]
    Hq, Hkv, hd = (m["num_attention_heads"], m["num_key_value_heads"],
                   m["head_dim"])
    V = m["vocab_size"]
    bf, f32 = jnp.bfloat16, jnp.float32
    s = {
        "embed": ((V, D), bf, 0.02),
        "ln1": ((L, D), f32, 0.1),
        "wq": ((L, D, Hq * hd), bf, D ** -0.5),
        "wk": ((L, D, Hkv * hd), bf, D ** -0.5),
        "wv": ((L, D, Hkv * hd), bf, D ** -0.5),
        "wo": ((L, Hq * hd, D), bf, (Hq * hd) ** -0.5),
        "w_up": ((L, D, F), bf, D ** -0.5),
        "w_down": ((L, F, D), bf, F ** -0.5),
        "final_norm": ((D,), f32, 0.1),
    }
    if m["mlp"] == "silu_glu":
        s["w_gate"] = ((L, D, F), bf, D ** -0.5)
    if m["qkv_bias"]:
        s["bq"] = ((L, Hq * hd), f32, 0.1)
        s["bk"] = ((L, Hkv * hd), f32, 0.1)
        s["bv"] = ((L, Hkv * hd), f32, 0.1)
    if m["block"] == "sequential":
        s["ln2"] = ((L, D), f32, 0.1)
    if not m["tie_word_embeddings"]:
        s["lm_head"] = ((D, V), bf, D ** -0.5)
    return s


def make(m: dict, seed: int) -> dict:
    """Every weight of the model, drawn from ``seed`` on the default device
    in one jitted call."""
    spec = shapes(m)
    names = sorted(spec)

    def init(key):
        keys = jax.random.split(key, len(names))
        out = {}
        for k, name in zip(keys, names):
            shape, dtype, std = spec[name]
            out[name] = (jax.random.normal(k, shape, dtype)
                         * jnp.asarray(std, dtype))
        return out

    return jax.jit(init)(jax.random.PRNGKey(seed % (2 ** 31 - 1)))


def n_params(m: dict) -> int:
    return sum(math.prod(shape) for shape, _, _ in shapes(m).values())


def program_tree(w: dict, m: dict) -> dict:
    """The program's parameter tree (``repro.models.transformer``'s layout:
    one scan group of identical layers) over the same buffers."""
    attn = {k: w[k] for k in ("wq", "wk", "wv", "wo")}
    for k in ("bq", "bk", "bv"):
        if k in w:
            attn[k] = w[k]
    mlp = {"w_up": w["w_up"], "w_down": w["w_down"]}
    if "w_gate" in w:
        mlp["w_gate"] = w["w_gate"]
    layer = {"ln1": {"scale": w["ln1"]}, "attn": attn, "mlp": mlp}
    if "ln2" in w:
        layer["ln2"] = {"scale": w["ln2"]}
    tree = {"embed": {"tok": w["embed"]}, "stack": [{"u0": layer}],
            "final_norm": {"scale": w["final_norm"]}}
    if "lm_head" in w:
        tree["lm_head"] = w["lm_head"]
    return tree
