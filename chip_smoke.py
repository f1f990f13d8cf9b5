#!/usr/bin/env python3
"""Chip smoke test: qwen2.5-3b at its published widths, served on a TPU.

    python chip_smoke.py               # one chip: kernels + serving engine
    python chip_smoke.py --four-chips  # 2x2-mesh engine against one chip

One process drives the chip.  Weights are random bf16 from a seed; nothing
is downloaded.  The one-chip run checks the Pallas kernels against the f32
reference at the model's widths, drains requests through the same engine
``python -m repro.launch.serve`` builds (``impl="flash"``), and counts the
Mosaic kernels in the compiled decode step and packed prefill.  Timings it
prints are smoke timings, not benchmark numbers.

The last line of stdout is ``{"ok": true, "device": {...}}``, printed only
when every check passed.  Without a TPU, or with a failed check, the script
exits non-zero and prints no such line.
"""
import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

from repro.launch import serve  # noqa: E402  (needs the repo's src/)

ENGINE_ARGV = ["--arch", "qwen2.5-3b", "--impl", "flash", "--max-batch", "8",
               "--kv-len", "1024", "--max-new-tokens", "32", "--seed", "0"]
# prompts span several packed-prefill streams and chunked continuations
# past the 128-token chunk
N_REQUESTS, PROMPT_LO, PROMPT_HI = 12, 64, 701

# Kernel output against attention_ref in f32 (highest matmul precision),
# on bf16 operands: max |kernel - ref| over max |ref|.  The bf16 output
# rounds by 2^-9; a bf16 MXU pass over the f32 softmax weights, or over
# dequantised K/V, adds up to 2^-8 each.
ATTN_RTOL = 2e-2
# Four chips against one: max |logit difference| over the largest |logit|.
# Sharded matmuls sum in another order in bf16 across 36 layers; a wrong
# sharding or a dropped shard moves logits by O(1) of their range.
LOGITS_RTOL = 5e-2


class SmokeFailure(Exception):
    pass


def check(ok: bool, what: str):
    print(f"  [{'pass' if ok else 'FAIL'}] {what}", flush=True)
    if not ok:
        raise SmokeFailure(what)


def custom_calls(compiled) -> int:
    return compiled.as_text().count("tpu_custom_call")


class CompileClock:
    """Seconds and count of XLA backend compiles (persistent-cache reads
    included) and persistent-cache hits, from JAX's monitoring events."""

    def __init__(self):
        import jax

        self.seconds, self.compiles, self.hits = 0.0, 0, 0

        def on_duration(event, secs, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.seconds += secs
                self.compiles += 1

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def snapshot(self):
        return self.seconds, self.compiles, self.hits


def record_first_call(executor, name: str, store: dict):
    """Wrap ``executor.<name>`` (a jitted program) so its first call's
    arguments are kept in ``store[name]`` as shapes with shardings, and the
    non-donated ones as arrays — enough to lower the same program again."""
    import jax

    fn = getattr(executor, name)

    def call(*args):
        if name not in store:
            store[name] = (jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                               sharding=x.sharding), args),
                args[3:])
        return fn(*args)

    call.lower = fn.lower
    setattr(executor, name, call)


def drain(engine, seed: int = 0):
    reqs = serve.submit_prompts(engine, N_REQUESTS, PROMPT_LO, PROMPT_HI,
                                seed)
    t0 = time.perf_counter()
    engine.run_until_drained()
    return reqs, time.perf_counter() - t0


def check_drained(reqs, max_new: int):
    from repro.serving.engine import DONE

    bad = [(r.uid, r.status, len(r.output)) for r in reqs
           if r.status != DONE or len(r.output) != max_new]
    check(not bad, f"{len(reqs)} requests DONE with {max_new} tokens each"
          + (f" (bad: {bad})" if bad else ""))


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------

def kernel_checks(cfg):
    """Decode (fp, kv8, kv4) and packed-segment prefill kernels at the
    model's widths against attention_ref in f32."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels.flash_attention.ops import attention
    from repro.kernels.flash_attention.ref import attention_ref
    from repro.quant.core import dequantize_kv, quantize_kv

    Hq, Hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    B, Skv = 8, 1024
    kq, kk, kv_ = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(kq, (B, 1, Hq, hd), jnp.bfloat16)
    k = jax.random.normal(kk, (B, Skv, Hkv, hd), jnp.bfloat16)
    v = jax.random.normal(kv_, (B, Skv, Hkv, hd), jnp.bfloat16)
    lengths = np.array([0, 1, 100, 128, 129, 700, 1000, 1024], np.int32)
    kv_pos = np.where(np.arange(Skv)[None] < lengths[:, None],
                      np.arange(Skv)[None], -1).astype(np.int32)
    q_pos = np.maximum(lengths - 1, 0)[:, None].astype(np.int32)
    f32 = lambda x: x.astype(jnp.float32)  # noqa: E731

    def ref(q, k, v, **kw):
        with jax.default_matmul_precision("highest"):
            return jax.jit(lambda q, k, v: attention_ref(
                f32(q), f32(k), f32(v), **kw))(q, k, v)

    def run(name, fn, expect, *args):
        compiled = jax.jit(fn).lower(*args).compile()
        out = compiled(*args)
        err = float(jnp.max(jnp.abs(f32(out) - expect))
                    / jnp.max(jnp.abs(expect)))
        n = custom_calls(compiled)
        check(n > 0 and err <= ATTN_RTOL,
              f"{name}: max|kernel - f32 ref| / max|ref| = {err:.3e} "
              f"(tol {ATTN_RTOL}), tpu_custom_call x{n}")

    dkw = dict(q_pos=q_pos, kv_pos=kv_pos, kv_valid=kv_pos >= 0, causal=True)
    run(f"decode fp   B={B} Skv={Skv}",
        lambda q, k, v: attention(q, k, v, impl="pallas", **dkw),
        ref(q, k, v, **dkw), q, k, v)
    for bits in (8, 4):
        k_q, k_s = quantize_kv(k, bits)
        v_q, v_s = quantize_kv(v, bits)
        expect = ref(q, dequantize_kv(k_q, k_s, bits),
                     dequantize_kv(v_q, v_s, bits), **dkw)
        run(f"decode kv{bits}  B={B} Skv={Skv}",
            lambda q, kq, ks, vq, vs, bits=bits: attention(
                q, kq, vq, k_scale=ks, v_scale=vs, kv_bits=bits,
                impl="pallas", **dkw),
            expect, q, k_q, k_s, v_q, v_s)

    # packed prefill: four prompts and a pad tail in one 512-token stream
    S = 512
    seg = np.full((1, S), -1, np.int32)
    for i, (a, b) in enumerate([(0, 64), (64, 200), (200, 330), (330, 500)]):
        seg[0, a:b] = i
    qs = jax.random.normal(kq, (1, S, Hq, hd), jnp.bfloat16)
    ks = jax.random.normal(kk, (1, S, Hkv, hd), jnp.bfloat16)
    vs = jax.random.normal(kv_, (1, S, Hkv, hd), jnp.bfloat16)
    real = jnp.asarray(seg[0] >= 0)[None, :, None, None]
    expect = jnp.where(real, ref(qs, ks, vs, q_seg=seg, kv_seg=seg,
                                 causal=True), 0.0)
    run(f"packed prefill S={S}",
        lambda q, k, v: jnp.where(real, attention(
            q, k, v, segments=seg, causal=True, impl="pallas"), 0.0),
        expect, qs, ks, vs)


def one_chip():
    args = serve.parse_args(ENGINE_ARGV)
    clock = CompileClock()
    cfg, engine = serve.build_engine(args)
    print(f"model {cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
          f"heads {cfg.n_heads}/{cfg.n_kv_heads}x{cfg.head_dim}, "
          f"d_ff {cfg.d_ff}, vocab {cfg.vocab_size}")
    kernel_checks(cfg)

    ex = engine.executor
    seen: dict = {}
    for name in ("jit_step", "jit_packed_prefill", "jit_chunk_step"):
        record_first_call(ex, name, seen)

    c0 = clock.snapshot()
    reqs, cold_s = drain(engine)
    c1 = clock.snapshot()
    check_drained(reqs, args.max_new_tokens)
    warm, warm_s = drain(engine)
    c2 = clock.snapshot()
    check_drained(warm, args.max_new_tokens)
    check([r.output for r in warm] == [r.output for r in reqs],
          "second drain repeats the first's greedy tokens")

    for name in ("jit_step", "jit_packed_prefill", "jit_chunk_step"):
        check(name in seen, f"engine ran {name}")
        n = custom_calls(getattr(ex, name).lower(*seen[name][0]).compile())
        print(f"  {name}: tpu_custom_call x{n}")
        if name != "jit_chunk_step":   # the chunk step has no kernel route
            check(n > 0, f"{name} runs a Pallas kernel")

    tokens = N_REQUESTS * args.max_new_tokens
    print("smoke timings (one run, not benchmark numbers):")
    print(f"  first drain: {cold_s:.2f} s, of which XLA compile "
          f"{c1[0] - c0[0]:.2f} s over {c1[1] - c0[1]} programs "
          f"({c1[2] - c0[2]} persistent-cache hits)")
    print(f"  second drain: {warm_s:.2f} s, {tokens / warm_s:.1f} tok/s "
          f"({c2[1] - c1[1]} compiles)")
    print(f"  compile over the whole run: {clock.seconds:.2f} s, "
          f"{clock.compiles} programs, {clock.hits} persistent-cache hits")


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------

def four_chips():
    """The same requests through a (data=2, model=2) mesh engine and a
    one-chip engine.  2x2 because qwen2.5-3b's 2 KV heads divide a model
    axis of 2 but not of 4: the decode plan then shards attention by head
    and the 8 KV slots over data."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.models import transformer as T
    from repro.parallel.api import activate_plan

    check(len(jax.devices()) >= 4, f"{len(jax.devices())} devices >= 4")
    args = serve.parse_args(ENGINE_ARGV)

    def run(mesh, first_stream=None, pool=None):
        cfg, engine = serve.build_engine(args, mesh=mesh)
        ex, seen = engine.executor, {}
        for name in ("jit_step", "jit_packed_prefill"):
            record_first_call(ex, name, seen)
        reqs, secs = drain(engine)
        check_drained(reqs, args.max_new_tokens)
        stream = first_stream or seen["jit_packed_prefill"][1]

        def prefill_logits(params, tokens, positions, seg, gather):
            with activate_plan(ex._prefill_plan):
                return T.prefill_packed(params, cfg, tokens, positions, seg,
                                        gather, impl=args.impl)[0]

        def decode_logits(params, cache, tokens, pos):
            with activate_plan(ex._plan):
                return T.decode_step(params, cfg, cache, tokens, pos,
                                     impl=args.impl)[0]

        first = jax.jit(prefill_logits)(ex.params, *stream[:4])
        if pool is None:
            pool = jax.device_get((engine.pool.cache, engine.pool.state))
        cache = jax.device_put(pool[0], jax.tree.map(
            lambda x: x.sharding, engine.pool.cache))
        dec = jax.jit(decode_logits)(ex.params, cache,
                                     jnp.asarray(pool[1]["tokens"]),
                                     jnp.asarray(pool[1]["pos"]))
        text = ex.jit_step.lower(*seen["jit_step"][0]).compile().as_text()
        print(f"  mesh {dict(mesh.shape) if mesh else 'none'}: drain "
              f"{secs:.2f} s; fused step tpu_custom_call x"
              f"{text.count('tpu_custom_call')}, all-gather x"
              f"{text.count('all-gather(')}, all-reduce x"
              f"{text.count('all-reduce(')}")
        active = np.asarray(stream[-1])
        out = ([r.output for r in reqs], np.asarray(first)[active],
               np.asarray(dec), stream, pool)
        for x in jax.tree.leaves(ex.params):  # free the chip for the next run
            x.delete()
        return out

    toks1, first1, dec1, stream, pool = run(None)
    toks4, first4, dec4, _, _ = run(serve.parse_mesh("2x2"), stream, pool)

    agree = np.mean([a == b for s1, s4 in zip(toks1, toks4)
                     for a, b in zip(s1, s4)])
    same = sum(s1 == s4 for s1, s4 in zip(toks1, toks4))
    print(f"  greedy token agreement: {agree:.4f} "
          f"({same}/{len(toks1)} streams identical)")
    for name, a, b in (("first packed-prefill step", first1, first4),
                       ("decode step on the one-chip final pool", dec1, dec4)):
        diff = float(np.max(np.abs(a.astype(np.float32)
                                   - b.astype(np.float32))))
        scale = float(np.max(np.abs(a.astype(np.float32))))
        check(diff <= LOGITS_RTOL * scale,
              f"{name} logits: max|2x2 - 1 chip| = {diff:.4f} against "
              f"max|logit| {scale:.4f} (tol {LOGITS_RTOL} x)")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 2x2-mesh engine against one chip")
    opts = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (jax finds {dev.platform}); not running",
              file=sys.stderr)
        return 2
    print(f"device: {dev.device_kind} x{len(devices)} ({dev.platform}), "
          f"compile cache {serve.enable_compile_cache()}", flush=True)
    try:
        four_chips() if opts.four_chips else one_chip()
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
