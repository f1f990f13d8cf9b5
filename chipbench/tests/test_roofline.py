import jax
import jax.numpy as jnp
import pytest

from conftest import REPO
from harness import roofline, spec

DENSE = spec.family(REPO, "dense")

QWEN = {"num_hidden_layers": 36, "hidden_size": 2048,
        "intermediate_size": 11008, "num_attention_heads": 16,
        "num_key_value_heads": 2, "head_dim": 128, "vocab_size": 151936,
        "mlp": "silu_glu"}


def test_decode_attention_by_hand():
    f, b = roofline.decode_attention([10], QWEN)
    assert f == 2 * 16 * 256 * 10                 # QK^T and PV, 10 keys
    assert b == 2 * (10 * 2 * 2 * 128 + 2 * 16 * 128)
    f2, b2 = roofline.decode_attention([10, 30], QWEN)
    assert f2 == 4 * f and b2 == b + 2 * (30 * 2 * 2 * 128 + 2 * 16 * 128)


def test_prefill_attention_counts_causal_pairs_only():
    f, b = roofline.prefill_attention([(0, 4)], QWEN)
    assert f == 2 * 16 * 256 * 10                 # 4 + 3 + 2 + 1 pairs
    assert b == 2 * 4 * (2 * 16 * 128 + 2 * 2 * 128)
    f2, _ = roofline.prefill_attention([(3, 2)], QWEN)
    assert f2 == 2 * 16 * 256 * (4 + 5)           # positions 3 and 4


def test_model_flops_per_token():
    per_layer = 2048 * (16 * 128 + 2 * 2 * 128) + 16 * 128 * 2048 \
        + 3 * 2048 * 11008
    assert DENSE.matmul_params_per_layer(QWEN) == per_layer
    f = roofline.model_flops(DENSE, QWEN, [(99, 1)], 1)  # one token at 100
    assert f == (2 * per_layer * 36 + 2 * 16 * 256 * 100 * 36
                 + 2 * 2048 * 151936)


def test_unknown_device_is_an_error():
    assert roofline.peaks("TPU v5 lite")["bf16_flop_per_s"] == 197e12
    with pytest.raises(KeyError):
        roofline.peaks("TPU v9 imaginary")


def test_needed_attention_flops_never_exceed_a_plain_implementation():
    """The needed count is at most what XLA counts for a plain attention
    over exactly the live keys, and not far below it: a share computed
    from it cannot pass 100% by over-counting."""
    m = dict(QWEN, num_attention_heads=4, num_key_value_heads=2,
             head_dim=64)
    n = 300

    def attend(q, k, v):              # one query, n live keys, GQA 2:1
        q = q.reshape(2, 2, 64)
        s = jnp.einsum("grd,kgd->grk", q, k)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("grk,kgd->grd", p, v)

    args = (jnp.zeros((4, 64)), jnp.zeros((n, 2, 64)), jnp.zeros((n, 2, 64)))
    xla = jax.jit(attend).lower(*args).compile().cost_analysis()
    xla = xla[0] if isinstance(xla, list) else xla
    need, _ = roofline.decode_attention([n], m)
    assert 0.9 * xla["flops"] <= need <= xla["flops"]
