"""The plain reference: the configuration's forward pass in float32 with
the highest matmul precision, written from the configuration file alone.

It imports nothing of the program.  It runs one sequence at a time, layer
by layer (one compiled layer function, the layer index an argument), so
that it fits beside the weights once the program's KV pool is freed.
This file holds what every family shares: the padding, the embedding,
the layer loop, the final norm, logits in row blocks and the readings.
The layer body and the matrices a control rounds are the family's
(``chipbench/models/<family>.py``), which may use ``_mm``, ``_norm`` and
``_rope`` from here.

``gaps`` is the comparison that decides ``correct``: for every served
token, how far the reference's logit of that token lies below the
reference's best logit at that position.  Greedy serving in a lower
precision picks a token whose reference logit is at most a rounding error
below the best; a wrong token lies below it by the spread of the logits.

``control_gaps`` is the control the limit is tested against: the same
reference computed with its weights rounded to a lower precision, reading
the gap of the token that the lower precision puts first.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
PAD = 256            # sequences are padded to a multiple of this length
ROWS = 256           # rows of logits computed at once


def _mm(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


def _lower(w, quant):
    """``w`` (f32) rounded as a lower precision would hold it: ``int8``
    with one scale per output column, or ``fp8`` (e4m3) with one scale per
    matrix."""
    if quant is None:
        return w
    if quant == "int8":
        s = jnp.max(jnp.abs(w), axis=-2, keepdims=True) / 127.0
        s = jnp.where(s == 0, 1.0, s)
        return jnp.round(w / s) * s
    if quant == "fp8":
        s = jnp.max(jnp.abs(w)) / 448.0
        return (w / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    raise ValueError(f"unknown control precision {quant!r}")


def _norm(x, g, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + g)


def _rope(x, pos, theta, rot):
    """Rotate-half rotary embedding over the first ``rot`` dims of each
    head.  ``x`` (S, H, hd), ``pos`` (S,)."""
    half = rot // 2
    freqs = theta ** (-jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)
    ang = pos.astype(jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:rot]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[..., rot:]], axis=-1)


# weights outside the layer stack, read by the scaffolding here
OUTSIDE = ("embed", "lm_head", "final_norm")


@functools.partial(jax.jit, static_argnames=("fam", "mkey", "quant"))
def _layer(x, w, l, *, fam, mkey, quant):
    """Layer ``l`` of the family's reference: its weights sliced from the
    stack in f32, the family's ``MATRICES`` rounded for a control."""
    lw = {}
    for name in w:
        if name in OUTSIDE:
            continue
        a = jax.lax.dynamic_index_in_dim(w[name], l, keepdims=False)
        a = a.astype(jnp.float32)
        lw[name] = _lower(a, quant) if name in fam.MATRICES else a
    return fam.layer(x, lw, jnp.arange(x.shape[0]), dict(mkey))


@functools.partial(jax.jit, static_argnames=("mkey",))
def _embed(w, tokens, *, mkey):
    return jnp.take(w["embed"], tokens, axis=0).astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("mkey",))
def _final(w, x, rows, *, mkey):
    m = dict(mkey)
    h = jnp.take(x, rows, axis=0)
    return _norm(h, w["final_norm"].astype(jnp.float32), m["norm_eps"])


def _key(m):
    return tuple(sorted((k, v) for k, v in m.items()
                        if not isinstance(v, (list, dict))))


def hidden(fam, w: dict, m: dict, seq: np.ndarray, rows: np.ndarray,
           quant=None) -> jax.Array:
    """Final-normed hidden states at ``rows`` of ``seq`` (f32)."""
    S = len(seq)
    Sp = -(-S // PAD) * PAD
    toks = np.zeros(Sp, np.int32)
    toks[:S] = seq
    mkey = _key(m)
    x = _embed(w, jnp.asarray(toks), mkey=mkey)
    for l in range(m["num_hidden_layers"]):
        x = _layer(x, w, jnp.int32(l), fam=fam, mkey=mkey, quant=quant)
    return _final(w, x, jnp.asarray(rows, jnp.int32), mkey=mkey)


@functools.partial(jax.jit, static_argnames=("mkey", "quant"))
def _logits(w, h, *, mkey, quant):
    m = dict(mkey)
    if m["tie_word_embeddings"]:
        head = w["embed"].astype(jnp.float32).T
    else:
        head = w["lm_head"].astype(jnp.float32)
    return _mm(h, _lower(head, quant))


@functools.partial(jax.jit, static_argnames=("mkey",))
def _gap_at(w, h, toks, *, mkey):
    lg = _logits(w, h, mkey=mkey, quant=None)
    at = jnp.take_along_axis(lg, toks[:, None], axis=1)[:, 0]
    return jnp.max(lg, axis=1) - at


def _blocks(h):
    n = h.shape[0]
    for i in range(0, n, ROWS):
        yield i, h[i:i + ROWS]


def _served(prompt, served):
    """The sequence the reference reads (the prompt and every served token
    but the last) and the rows whose logits chose each served token."""
    seq = np.concatenate([np.asarray(prompt, np.int32),
                          np.asarray(served[:-1], np.int32)])
    rows = np.arange(len(prompt) - 1, len(seq))
    return seq, rows


def gaps(fam, w: dict, m: dict, prompt, served) -> np.ndarray:
    """Per served token: the reference's best logit minus its logit of
    that token, at the position that chose it."""
    seq, rows = _served(prompt, served)
    h = hidden(fam, w, m, seq, rows)
    toks = np.asarray(served, np.int32)
    mkey = _key(m)
    out = [np.asarray(_gap_at(w, hb, jnp.asarray(toks[i:i + hb.shape[0]]),
                              mkey=mkey)) for i, hb in _blocks(h)]
    return np.concatenate(out)


def control_gaps(fam, w: dict, m: dict, prompt, served,
                 quant: str) -> np.ndarray:
    """At each position of the same prompt and served tokens, the gap (in
    the f32 reference) of the token that the ``quant`` reference puts
    first."""
    seq, rows = _served(prompt, served)
    mkey = _key(m)
    h_ref = hidden(fam, w, m, seq, rows)
    h_low = hidden(fam, w, m, seq, rows, quant=quant)
    out = []
    for (i, hr), (_, hl) in zip(_blocks(h_ref), _blocks(h_low)):
        pick = jnp.argmax(_logits(w, hl, mkey=mkey, quant=quant), axis=1)
        out.append(np.asarray(_gap_at(w, hr, pick.astype(jnp.int32),
                                      mkey=mkey)))
    return np.concatenate(out)
