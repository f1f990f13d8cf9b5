"""Weights made over a stated mesh, on four host CPU devices in a child
process (this process keeps one): a tiny cell on ``chips: 4``, mesh data 2
x model 2.  The run is correct, the engine runs on that mesh, every weight
is replicated whole on each of the four devices, and the values are those
of the weights made on one device from the same seed."""
import json
import os
import subprocess
import sys
from pathlib import Path

SEED = 2 ** 33 + 5


def test_a_four_chip_cell_runs_correct_with_weights_over_its_mesh(
        tiny_root, tmp_path):
    from test_families import _with_config

    root = _with_config(tiny_root, tmp_path, "tiny-mesh", 4,
                        mesh={"data": 2, "model": 2})
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, __file__, str(root)], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    got = json.loads(p.stdout.strip().splitlines()[-1])
    assert got["correct"], got["check"]
    assert got["device"]["count"] == 4
    assert got["shards"] == got["shares"]
    assert got["same_values"]
    assert got["engine_on_mesh"] == {"data": 2, "model": 2}


def _child(root: Path) -> dict:
    import time

    import jax
    import numpy as np

    from harness import cell, spec, weights

    bench = spec.load(root)
    c = spec.cell(bench, "tiny-mesh.tiny-closed")
    devices = jax.devices()[:c["chips"]]
    seen = {}

    def look(engine):
        seen.update(engine.executor._plan.mesh.shape)

    res = cell.run(root, bench, c, seed=SEED, seconds=2, traced=False,
                   t_start=time.monotonic(), devices=devices, patch=look,
                   drain_s=5.0)
    conf, fam = spec.check(root, bench, c)
    m = conf["model"]
    mesh = weights.mesh(conf, devices)
    placed = weights.make(fam, m, SEED, mesh)
    plain = weights.make(fam, m, SEED)
    shards, shares = {}, {}
    for name, (shape, _, _) in fam.shapes(m).items():
        shards[name] = sorted((s.device.id, tuple(s.data.shape))
                              for s in placed[name].addressable_shards)
        shares[name] = [(d.id, tuple(shape)) for d in sorted(
            devices, key=lambda d: d.id)]
    same = all(np.array_equal(np.asarray(placed[k]), np.asarray(plain[k]))
               for k in plain)
    return {"correct": res["correct"], "check": res["check"],
            "device": res["device"], "shards": shards, "shares": shares,
            "same_values": same, "engine_on_mesh": seen}


if __name__ == "__main__":
    repo = Path(__file__).resolve().parents[2]
    sys.path[:0] = [str(repo / "chipbench"), str(repo / "src")]
    out = _child(Path(sys.argv[1]))
    print(json.dumps(out, default=list))
