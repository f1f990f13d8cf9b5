"""Serving launcher: ``python -m repro.launch.serve --arch <id> [...]``.

Brings up the slotted continuous-batching engine on the requested mesh
and drives a synthetic request workload (uniform prompt lengths),
reporting throughput / TTFT / latency — the serving-side analogue of
train.py.  ``chip_smoke.py`` at the repo root drives the same functions.
"""
import argparse
import os
from pathlib import Path

# fixed, so that every process of this checkout finds the same entries:
# the directory is part of the persistent cache's key
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, where set, is honoured as JAX reads it;
    otherwise the cache lives at ``<repo>/.jax_cache``.  Call it from an
    entry point, before the first compile — never at import."""
    import jax

    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = str(REPO_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_enable_compilation_cache", True)
    return cache_dir


def parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--mesh", default="",
                    help="DATAxMODEL device mesh over jax.devices(), "
                         "e.g. 2x2 (default: one device, no mesh)")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--kv-len", type=int, default=128)
    ap.add_argument("--max-new-tokens", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--impl", default="ref",
                    choices=["ref", "auto", "flash", "pallas",
                             "pallas_interpret"],
                    help="attention impl (flash = Pallas decode kernel)")
    ap.add_argument("--decode-chunk", type=int, default=1,
                    help="device decode iterations per host sync")
    ap.add_argument("--host-loop", action="store_true",
                    help="use the legacy host-looped step (fused=False)")
    ap.add_argument("--weight-bits", type=int, default=0, choices=[0, 4, 8],
                    help="weight-only quantisation (0 = native fp)")
    ap.add_argument("--kv-bits", type=int, default=0, choices=[0, 4, 8],
                    help="quantised slot-pool KV cache (0 = fp pool)")
    return ap.parse_args(argv)


def parse_mesh(spec: str):
    """``"DxM"`` -> a ``(data, model)`` mesh over the first D*M devices of
    ``jax.devices()``; ``""`` -> None (single device)."""
    if not spec:
        return None
    from repro.launch.mesh import small_mesh

    try:
        data, model = (int(n) for n in spec.lower().split("x"))
    except ValueError:
        raise SystemExit(f"--mesh wants DATAxMODEL, e.g. 2x2; got {spec!r}")
    return small_mesh(data, model)


def build_engine(args, *, mesh=None):
    """``(cfg, engine)`` for parsed launcher ``args``: random bf16 weights
    from ``args.seed`` and a :class:`ServingEngine` on ``mesh`` (weights
    replicated over it; the engine's plans shard activations and KV)."""
    import jax
    import jax.numpy as jnp
    from repro.config import get_config, reduce_config
    from repro.models import transformer as T
    from repro.serving.engine import EngineConfig, ServingEngine

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduce_config(cfg)
    if cfg.family == "encoder":
        raise SystemExit("encoder-only architectures have no decode step")

    params = T.init_params(cfg, jax.random.PRNGKey(args.seed),
                           param_dtype=jnp.bfloat16)
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec
        params = jax.device_put(params, NamedSharding(mesh, PartitionSpec()))
    engine = ServingEngine(cfg, params, EngineConfig(
        max_batch=args.max_batch, kv_len=args.kv_len,
        max_new_tokens=args.max_new_tokens, temperature=args.temperature,
        seed=args.seed, impl=args.impl, fused=not args.host_loop,
        decode_chunk=args.decode_chunk,
        weight_bits=args.weight_bits, kv_bits=args.kv_bits), mesh=mesh)
    return cfg, engine


def submit_prompts(engine, n: int, lo: int, hi: int, seed: int) -> list:
    """Submit ``n`` random-token prompts with lengths uniform in
    ``[lo, hi)``, all drawn from ``seed``; returns the requests."""
    import numpy as np

    rng = np.random.default_rng(seed)
    lengths = rng.integers(lo, hi, size=n)
    return [engine.submit(rng.integers(0, engine.cfg.vocab_size, size=plen))
            for plen in lengths]


def main(argv=None):
    args = parse_args(argv)
    enable_compile_cache()
    cfg, engine = build_engine(args, mesh=parse_mesh(args.mesh))
    submit_prompts(engine, args.requests, 4,
                   min(64, args.kv_len - args.max_new_tokens - 1), args.seed)

    engine.run_until_drained()
    stats = engine.stats()
    bits = (f"w{args.weight_bits or 'fp'}/kv{args.kv_bits or 'fp'} "
            if (args.weight_bits or args.kv_bits) else "")
    print(f"arch={cfg.name} {bits}requests={stats['finished']} "
          f"tokens={stats['tokens']} "
          f"throughput={stats['tokens_per_s']:.1f} tok/s "
          f"ttft={stats['mean_ttft_s']*1e3:.0f}ms "
          f"latency={stats['mean_latency_s']*1e3:.0f}ms")


if __name__ == "__main__":
    main()
