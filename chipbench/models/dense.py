"""Family ``dense``: decoder layers of GQA attention (rotary over the whole
head, optional qkv bias) and one MLP (SiLU-gated or GELU), in a sequential
or a parallel block, with RMS norms.  The program's
``repro.models.transformer`` layer of kind ``global``.

A family file gives the harness everything that depends on the layer's
equations: the weights in the benchmark's layout (``shapes``), the
program's tree over them (``program_tree``), the program fields the
configuration fixes (``program_keys``), the plain float32 layer body
(``layer``), the matrices the control rounds (``MATRICES``) and the
weights one token uses (``matmul_params_per_layer``).  It imports nothing
of the program.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from harness.reference import HIGHEST, _mm, _norm, _rope

MATRICES = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")

ARCH_KEYS = {                 # model block key -> program ModelConfig field
    "num_hidden_layers": "n_layers", "hidden_size": "d_model",
    "intermediate_size": "d_ff", "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads", "head_dim": "head_dim",
    "vocab_size": "vocab_size", "rope_theta": "rope_theta",
    "qkv_bias": "qkv_bias", "tie_word_embeddings": "tie_embeddings",
}


def shapes(m: dict) -> dict:
    """``{name: (shape, dtype, std)}`` of every weight, layers stacked on
    the first axis; a norm gain is stored as ``g`` and applied as
    ``1 + g``.  Projections are bf16, the type they are served in; norm
    gains and qkv biases are f32, as the program keeps them."""
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


def program_tree(w: dict, m: dict) -> dict:
    """The program's parameter tree (one scan group of identical layers)
    over the same buffers."""
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


def program_keys(m: dict, cfg) -> dict:
    """The fields of the program's ``ModelConfig`` that the model block
    fixes, with the values it states; raises where ``cfg`` holds others,
    so the file states what runs."""
    want = {v: m[k] for k, v in ARCH_KEYS.items()}
    want["parallel_block"] = m["block"] == "parallel"
    want["glu"] = m["mlp"] == "silu_glu"
    want["act"] = "silu" if m["mlp"] == "silu_glu" else "gelu"
    want["norm"] = m["norm"]
    want["v_head_dim"] = m["head_dim"]
    want["pattern"] = ("global",)
    got = {k: getattr(cfg, k) for k in want}
    if got != want or m["rotary_dim"] != m["head_dim"]:
        raise ValueError(f"the program's configuration is {got}; the file "
                         f"states {want}, rotary_dim {m['rotary_dim']}")
    return want


def _mlp(h, lw, m):
    if m["mlp"] == "silu_glu":
        a = jax.nn.silu(_mm(h, lw["w_gate"])) * _mm(h, lw["w_up"])
    elif m["mlp"] == "gelu_tanh":
        a = jax.nn.gelu(_mm(h, lw["w_up"]), approximate=True)
    else:
        raise ValueError(f"unknown mlp {m['mlp']!r}")
    return _mm(a, lw["w_down"])


def layer(x, lw: dict, pos, m: dict):
    """One layer of the f32 reference over a whole sequence: ``x`` (S, D),
    ``lw`` this layer's weights in f32, ``pos`` (S,) the positions."""
    S = x.shape[0]
    Hq, Hkv, hd = (m["num_attention_heads"], m["num_key_value_heads"],
                   m["head_dim"])
    eps = m["norm_eps"]
    h = _norm(x, lw["ln1"], eps)
    q, k, v = _mm(h, lw["wq"]), _mm(h, lw["wk"]), _mm(h, lw["wv"])
    if m["qkv_bias"]:
        q, k, v = q + lw["bq"], k + lw["bk"], v + lw["bv"]
    q = _rope(q.reshape(S, Hq, hd), pos, m["rope_theta"], m["rotary_dim"])
    k = _rope(k.reshape(S, Hkv, hd), pos, m["rope_theta"], m["rotary_dim"])
    v = v.reshape(S, Hkv, hd)
    q = q.reshape(S, Hkv, Hq // Hkv, hd)
    s = jnp.einsum("qgrd,kgd->grqk", q, k, precision=HIGHEST) * hd ** -0.5
    s = jnp.where(pos[None, None, None, :] <= pos[None, None, :, None], s,
                  -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("grqk,kgd->qgrd", p, v, precision=HIGHEST)
    a = _mm(o.reshape(S, Hq * hd), lw["wo"])
    if m["block"] == "parallel":
        return x + a + _mlp(h, lw, m)
    x = x + a
    return x + _mlp(_norm(x, lw["ln2"], eps), lw, m)


def matmul_params_per_layer(m: dict) -> int:
    """Weights of one layer's projections that one token multiplies."""
    d, f = m["hidden_size"], m["intermediate_size"]
    hq, hkv = m["num_attention_heads"], m["num_key_value_heads"]
    hd = m["head_dim"]
    attn = d * (hq * hd + 2 * hkv * hd) + hq * hd * d
    mlp = d * f * (3 if m["mlp"] == "silu_glu" else 2)
    return attn + mlp
