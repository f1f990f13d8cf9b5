"""Chip benchmark: one cell of ``BENCHMARK.json`` in one process, on the
chips the cell asks for.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Loads the cell's configuration and its model family
(``chipbench/models/<family>.py``), and checks that the configuration's
mesh spans the cell's chips (no mesh: one chip).  Makes the weights from
the seed on the chip, or replicated over the mesh, builds the engine on
that mesh, warms the programs its traffic runs, offers its traffic for
the mix's warm-up and then measures for ``--seconds``.  With
``--trace 1`` the profiler records a steady slice in the middle of the
window, and the run reports the cell's per-layer metrics in place of its
end-to-end ones.

Once the window has closed and the program's KV pool is freed, a sample of
the finished requests, drawn from the seed with the longest among them, is
run through the plain float32 reference, and ``correct`` says whether
every served token's reference logit lies within the configuration's
limit of the reference's best.

``CHIPBENCH_KEEP_TRACE=<dir>`` keeps a traced run's raw ``.xplane.pb``
there, for reading by hand.

The last line of stdout is one JSON object (``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, traced: ``breakdown``, and last the
numbers compared beside their limits under ``check``).  Without a TPU, or
with fewer chips than the cell asks for, it exits non-zero and prints no
such line.
"""
import time

T_START = time.monotonic()   # set-up is counted from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "chipbench"))

from harness import spec  # noqa: E402


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache at ``JAX_COMPILATION_CACHE_DIR``
    where set, else at the fixed ``<checkout>/.jax_cache``, for every
    program however quick to compile."""
    import jax

    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = str(ROOT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_enable_compilation_cache", True)
    # every program, however quick to compile, so a second run compiles
    # nothing (the reference's layer programs among them)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return cache_dir


def main(argv=None) -> int:
    args = parse_args(argv)
    bench = spec.load(ROOT)
    cell = spec.cell(bench, args.workload)
    spec.check(ROOT, bench, cell)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        print(f"chipbench: needs {cell['chips']} TPU chip(s); jax finds "
              f"{len(devices)} {devices[0].platform} device(s). Not running.",
              file=sys.stderr)
        return 2
    from harness import cell as cellrun

    cache = enable_compile_cache()
    print(f"device {devices[0].device_kind} x{len(devices)}, compile cache "
          f"{cache}", file=sys.stderr, flush=True)
    result = cellrun.run(ROOT, bench, cell, seed=args.seed,
                         seconds=args.seconds, traced=bool(args.trace),
                         t_start=T_START, devices=devices[:cell["chips"]])
    for name, c in result["check"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
