"""Readings that set a cell's correctness limit, on the chip, in one
process: the program's own over a dozen seeds or more, and the controls'.

    python3 chipbench/control.py --workload <cell> --seeds 1,2,...,12 \
        --control-seeds 1,2,3 --seconds 15

For each program seed: the cell's traffic for its warm-up and a short
window at its own load, then the harness's own check (``cell.check``) of
the sampled served tokens, as a run makes it.  On the control seeds the
same check is made again with a control in the program's place:

- ``int8``, the precision below the configuration's bf16: the reference
  with its weights rounded to int8 (one scale per output column), read at
  each position of the program's sampled prompts and served tokens by the
  gap of the token that it puts first.

A control has to come out ``correct`` false.  Each line prints the
program's ``correct`` and widest gap, and each control's.  The limit goes
between the program's largest reading and the smallest control reading;
the readings and the limit go into ``PERF.md``.  The program's own int8
weight path (``weight_bits=8``) is not among the controls: quantising
beside the benchmark's bf16 weights does not fit a 16 GiB chip at these
sizes.  Benchmark runs do not run this.
"""
import time

T_START = time.monotonic()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "chipbench"))

from harness import spec  # noqa: E402


def readings(root, bench, cell, seed, seconds, devices, controls=()):
    import functools

    from harness import cell as cellrun
    from harness import driver, reference
    from harness.traffic import RequestStream

    setup = cellrun.prepare(root, bench, cell, seed, devices)
    mix, m = setup.mix, setup.m
    stream = RequestStream(mix, seed, m["vocab_size"], setup.rate)
    run = driver.drive(setup.engine, stream, mix, seconds)
    att = [r for r in run.recs if run.w0 <= r.due < run.w1]
    failed = sum(1 for r in att if r.first is None)
    cellrun._free(setup.engine)
    setup.engine = None
    args = (setup.fam, setup.w, m, run.recs, int(mix["check_requests"]),
            seed, setup.conf["check"]["max_logit_gap"], failed,
            cellrun.stalled(run, float(mix["stall_s"])))
    ok, chk = cellrun.check(*args)
    out = {"seed": seed, "correct": ok,
           "max_logit_gap": chk["max_logit_gap"]["value"],
           "limit": chk["max_logit_gap"]["limit"],
           "failed": failed, "stalled": chk["stalled_requests"]["value"],
           "tokens": chk["tokens_compared"]["value"],
           "requests": [len(r.req.output) for r in cellrun.sample(
               run.recs, int(mix["check_requests"]), seed)]}
    for q in controls:
        ok, chk = cellrun.check(*args, gaps_of=functools.partial(
            reference.control_gaps, quant=q))
        out[f"control_{q}"] = {"correct": ok,
                               "max_logit_gap": chk["max_logit_gap"]["value"]}
    del setup, args
    gc.collect()
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=15.0)
    args = ap.parse_args(argv)
    bench = spec.load(ROOT)
    cell = spec.cell(bench, args.workload)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        print("control: not enough TPU chips; not running", file=sys.stderr)
        return 2
    import run as runmod

    runmod.enable_compile_cache()
    ctl = [int(s) for s in args.control_seeds.split(",") if s]
    for s in (int(s) for s in args.seeds.split(",")):
        r = readings(ROOT, bench, cell, s, args.seconds,
                     devices[:cell["chips"]],
                     controls=("int8",) if s in ctl else ())
        print(json.dumps(r), flush=True)
    print(f"control: {time.monotonic() - T_START:.1f} s", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
