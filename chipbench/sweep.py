"""Rate sweep of an open-loop cell: one process offers the cell's mix at a
rising series of rates and reports the knee, the highest rate the engine
sustains without a growing backlog.

    python3 chipbench/sweep.py --workload <cell> --seed <n> \
        --rates 1,1.5,2,2.5 --seconds 30

The weights are made once; each rate gets a fresh engine (its programs
from the compile cache), since the longest requests outlast a window.  A
rate is sustained when every request due in the window got its first token
and the queue of waiting requests at the end of the window is no longer
than at its start.  The cell runs at
about four fifths of the knee: write that rate, and this table, into the
cell's rate file and ``PERF.md`` by hand.
"""
import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "chipbench"))

from harness import spec  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    args = ap.parse_args(argv)
    bench = spec.load(ROOT)
    cell = spec.cell(bench, args.workload)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        print("sweep: not enough TPU chips; not running", file=sys.stderr)
        return 2
    import run as runmod
    from harness import cell as cellrun
    from harness import driver
    from harness.stats import percentile
    from harness.traffic import RequestStream

    runmod.enable_compile_cache()
    setup = cellrun.prepare(ROOT, bench, cell, args.seed,
                            devices[:cell["chips"]])
    mix, m, engine = setup.mix, setup.m, setup.engine
    if mix["loop"] != "open":
        raise SystemExit("sweep: only open-loop cells have a rate")
    rows = []
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        stream = RequestStream(mix, args.seed + i, m["vocab_size"], rate)
        q = {}
        run = driver.drive(
            engine, stream, mix, args.seconds,
            on_window=lambda: q.setdefault("q0", len(engine.queue)),
            on_close=lambda: q.setdefault("q1", len(engine.queue)),
            drain_s=10.0)
        q1 = q.get("q1", 0)
        e2e = cellrun.end_to_end(run)
        att = [r for r in run.recs if run.w0 <= r.due < run.w1]
        missing = sum(1 for r in att if r.first is None)
        row = {"rate_rps": rate, "attempted": len(att), "missing": missing,
               "queue_start": q.get("q0", 0), "queue_end": q1,
               "ttft_p50_ms": e2e["ttft_p50_ms"],
               "ttft_p90_ms": 1e3 * (percentile(
                   [r.first - r.due for r in att if r.first], 90) or 0),
               "itl_p95_ms": e2e["itl_p95_ms"],
               "output_tokens_per_s": e2e["output_tokens_per_s"]}
        row["sustained"] = missing == 0 and q1 <= row["queue_start"]
        rows.append(row)
        print(json.dumps(row), flush=True)
        cellrun._free(engine)           # a fresh pool for the next rate
        engine = setup.engine = setup.new_engine(args.seed)
        driver.warm(engine, mix["prompt"]["max"] > engine._chunk)
    ok = [r["rate_rps"] for r in rows if r["sustained"]]
    knee = max(ok) if ok else None
    print(json.dumps({"workload": cell["name"], "knee_rps": knee,
                      "rate_rps": None if knee is None else 0.8 * knee,
                      "rows": rows}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
