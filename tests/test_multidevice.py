"""Multi-device integration tests — each runs in a subprocess with forced
host devices so the main pytest process keeps seeing 1 CPU device."""
import json
import subprocess
import sys

import pytest

from conftest import REPO, subprocess_env


def _run(code: str, n_devices: int, timeout=600):
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=subprocess_env(n_devices), cwd=REPO,
                       timeout=timeout)
    if r.returncode != 0:
        raise AssertionError(f"subprocess failed:\n{r.stdout}\n{r.stderr}")
    return r.stdout


def test_sharded_train_step_matches_single_device():
    """The same train step on a 2×2 mesh and on one device must produce the
    same loss (sharding is semantics-preserving)."""
    out = _run("""
import jax, jax.numpy as jnp
from repro.config import get_config, reduce_config, ShapeSpec
from repro.launch.mesh import small_mesh
from repro.launch.steps import build_cell
from repro.models import transformer as T
from repro.training.optimizer import adamw_init

cfg = reduce_config(get_config("gemma2-9b"))
shape = ShapeSpec("t", "train", 32, 4)
mesh = small_mesh(2, 2)
jfn, specs, plan = build_cell(cfg, shape, mesh, donate=False)
params = T.init_params(cfg, jax.random.PRNGKey(0))
opt = adamw_init(params)
batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0, cfg.vocab_size)}
with mesh:
    _, _, m_sharded = jfn(params, opt, batch)

from repro.launch.steps import make_train_step
fn = make_train_step(cfg)
_, _, m_single = jax.jit(fn)(params, opt, batch)
d = abs(float(m_sharded["loss"]) - float(m_single["loss"]))
assert d < 5e-2, d
print("OK", float(m_sharded["loss"]), float(m_single["loss"]))
""", 4)
    assert "OK" in out


def test_elastic_remesh_8_to_4():
    """Train 3 steps on 8 devices, re-mesh to 4, continue — loss stream
    must keep descending and state must re-shard without error."""
    out = _run("""
import jax
from repro.config import get_config, reduce_config, ShapeSpec
from repro.launch.mesh import small_mesh
from repro.training.trainer import Trainer, TrainerConfig
from repro.training.optimizer import OptConfig

cfg = reduce_config(get_config("qwen2.5-3b"))
shape = ShapeSpec("t", "train", 16, 8)
t = Trainer(cfg, shape, small_mesh(4, 2),
            opt_cfg=OptConfig(lr=5e-3, warmup_steps=0, total_steps=50),
            tcfg=TrainerConfig())
t.run(3)
l3 = t.metrics_log[-1]["loss"]
t.remesh(small_mesh(2, 2))     # elastic shrink: 8 -> 4 devices
t.run(3)
l6 = t.metrics_log[-1]["loss"]
assert t.step == 6
print("OK", l3, l6)
""", 8)
    assert "OK" in out


def test_elastic_remesh_matches_unremeshed():
    """Bitwise-ish: remeshing mid-run must not change the math — compare
    against an uninterrupted run on the original mesh."""
    out = _run("""
import jax
from repro.config import get_config, reduce_config, ShapeSpec
from repro.launch.mesh import small_mesh
from repro.training.trainer import Trainer, TrainerConfig
from repro.training.optimizer import OptConfig

cfg = reduce_config(get_config("qwen2.5-3b"))
shape = ShapeSpec("t", "train", 16, 8)
opt = OptConfig(lr=5e-3, warmup_steps=0, total_steps=50)

a = Trainer(cfg, shape, small_mesh(4, 2), opt_cfg=opt, tcfg=TrainerConfig())
a.run(2); a.remesh(small_mesh(2, 2)); a.run(2)

b = Trainer(cfg, shape, small_mesh(4, 2), opt_cfg=opt, tcfg=TrainerConfig())
b.run(4)

la = [m["loss"] for m in a.metrics_log]
lb = [m["loss"] for m in b.metrics_log]
diffs = [abs(x - y) for x, y in zip(la, lb)]
assert max(diffs) < 1e-3, (la, lb)
print("OK", diffs)
""", 8)
    assert "OK" in out


def test_overlap_collective_matmul():
    out = _run("""
import jax, jax.numpy as jnp
from repro.parallel.overlap import allgather_matmul, reduce_scatter_matmul
from repro.launch.mesh import small_mesh
mesh = small_mesh(1, 4)
k1, k2 = jax.random.split(jax.random.PRNGKey(0))
x = jax.random.normal(k1, (64, 32))
w = jax.random.normal(k2, (32, 48))
err = float(jnp.abs(allgather_matmul(x, w, mesh) - x @ w).max())
assert err < 1e-4, err
x2 = jax.random.normal(k1, (64, 128))
w2 = jax.random.normal(k2, (128, 48))
err2 = float(jnp.abs(reduce_scatter_matmul(x2, w2, mesh) - x2 @ w2).max())
assert err2 < 1e-4, err2
# HLO really contains collective-permute (ring), not all-gather
hlo = jax.jit(lambda a, b: allgather_matmul(a, b, mesh)).lower(x, w).compile().as_text()
assert "collective-permute" in hlo
print("OK", err, err2)
""", 4)
    assert "OK" in out


def test_grad_compression_pod_axis():
    """int8-compressed DP gradients still train (loss decreases) on a
    2-pod-like mesh."""
    out = _run("""
import jax, jax.numpy as jnp
from repro.config import get_config, reduce_config, ShapeSpec
from repro.launch.steps import make_train_step
from repro.models import transformer as T
from repro.training.optimizer import OptConfig, adamw_init, adamw_update
from repro.training.compression import compress_decompress
from repro.data.pipeline import DataConfig, LMDataPipeline

cfg = reduce_config(get_config("qwen2.5-3b"))
params = T.init_params(cfg, jax.random.PRNGKey(0))
opt = adamw_init(params)
ocfg = OptConfig(lr=5e-3, warmup_steps=0, total_steps=60)
pipe = LMDataPipeline(DataConfig(vocab_size=cfg.vocab_size, seq_len=16, global_batch=8))

def loss_f(p, batch):
    return T.loss_fn(p, cfg, batch)

err = None
losses = []
for step in range(15):
    batch = {k: jnp.asarray(v) for k, v in pipe.global_batch_at(step).items()}
    (l, _), g = jax.jit(jax.value_and_grad(loss_f, has_aux=True))(params, batch)
    g, err = compress_decompress(g, err)   # int8 + error feedback
    params, opt, _ = adamw_update(g, opt, params, ocfg)
    losses.append(float(l))
assert sum(losses[-3:]) < sum(losses[:3]) - 0.05, losses
print("OK", losses[0], losses[-1])
""", 2)
    assert "OK" in out


@pytest.mark.slow
def test_dryrun_one_cell_end_to_end(tmp_path):
    """launch/dryrun.py lowers+compiles one real cell on the 256-device
    production mesh (the cheapest assigned cell: mamba2-130m train_4k)."""
    import subprocess
    env = subprocess_env(1)  # dryrun sets its own XLA_FLAGS internally
    env.pop("XLA_FLAGS", None)
    # write the cell into the test tmp dir — a stray single-cell
    # experiments/dryrun/ would trip test_hetero's matrix-completeness check
    env["REPRO_DRYRUN_DIR"] = str(tmp_path)
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch", "mamba2-130m",
         "--shape", "train_4k", "--mesh", "single", "--force"],
        capture_output=True, text=True, env=env,
        cwd=str(REPO) + "/src", timeout=1800)
    assert "OK" in r.stdout, (r.stdout, r.stderr)


def test_sharded_slot_pool_serving_matches_single_device():
    """ServingEngine with a (data, model) mesh shards the KV slot pool and
    runs the fused decode step under the decode plan — outputs must match
    the unsharded engine exactly (greedy)."""
    out = _run("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh
from repro.config import get_config, reduce_config
from repro.models import transformer as T
from repro.serving.engine import EngineConfig, ServingEngine

cfg = reduce_config(get_config("qwen2.5-3b"))
params = T.init_params(cfg, jax.random.PRNGKey(0), param_dtype=jnp.float32)
mesh = Mesh(np.asarray(jax.devices()).reshape(1, 2), ("data", "model"))

def run(mesh=None):
    eng = ServingEngine(cfg, params,
                        EngineConfig(max_batch=2, kv_len=48, max_new_tokens=5),
                        mesh=mesh)
    rng = np.random.default_rng(0)
    for i in range(4):
        eng.submit(rng.integers(0, cfg.vocab_size, size=4 + i))
    eng.run_until_drained()
    return [r.output for r in sorted(eng.finished, key=lambda r: r.uid)]

a = run(None)
b = run(mesh)
assert a == b, (a, b)
print("OK", a[0])
""", 2)
    assert "OK" in out


def test_sharded_flash_engine_matches_single_device():
    """impl="flash" on a (data, model) = (2, 2) mesh: the Pallas decode and
    packed-prefill kernels run per device inside a shard_map (GSPMD cannot
    partition a Mosaic kernel) — slots split over data, heads over model —
    and the greedy streams match the unsharded flash engine."""
    out = _run("""
import jax, jax.numpy as jnp, numpy as np
from repro.config import get_config, reduce_config
from repro.launch.mesh import small_mesh
from repro.models import transformer as T
from repro.serving.engine import EngineConfig, ServingEngine

cfg = reduce_config(get_config("qwen2.5-3b"))
params = T.init_params(cfg, jax.random.PRNGKey(0), param_dtype=jnp.float32)

def run(mesh=None):
    eng = ServingEngine(cfg, params,
                        EngineConfig(max_batch=4, kv_len=128, max_new_tokens=5,
                                     impl="flash"), mesh=mesh)
    rng = np.random.default_rng(0)
    for i in range(6):
        eng.submit(rng.integers(0, cfg.vocab_size, size=4 + 7 * i))
    eng.run_until_drained()
    return [r.output for r in sorted(eng.finished, key=lambda r: r.uid)]

a = run(None)
b = run(small_mesh(2, 2))
assert a == b, (a, b)
print("OK", a[0])
""", 4)
    assert "OK" in out
