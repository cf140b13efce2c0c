"""Multi-device tests (subprocess with forced host device count): the
production sharding rules on a small mesh, pipeline parallelism, and
elastic checkpoint resharding across different mesh sizes."""
import os
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]


def run_py(code: str, devices: int = 8) -> str:
    env = {"XLA_FLAGS": f"--xla_force_host_platform_device_count={devices}",
           # keep the subprocess off any real accelerator: without this,
           # images that bundle libtpu stall for minutes retrying the GCP
           # TPU-metadata query before falling back to CPU
           "JAX_PLATFORMS": os.environ.get("JAX_PLATFORMS", "cpu"),
           "PYTHONPATH": str(REPO / "src"), "PATH": "/usr/bin:/bin"}
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=600)
    assert r.returncode == 0, r.stderr[-4000:]
    return r.stdout


def test_sharded_train_step_matches_single_device():
    """A real sharded train step on a (2,2,2) pod/data/model mesh produces
    the same loss as the unsharded computation."""
    out = run_py("""
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_config, reduced
from repro.models import build_model, init_params, make_shardings
from repro.models.params import abstract_params
from repro.runtime.sharding import activation_sharding, param_rules
from repro.runtime.training import TrainConfig, make_train_step, opt_state_specs
from repro.data.pipeline import DataConfig, SyntheticLM
from repro.launch.mesh import make_mesh

cfg = reduced(get_config("yi-6b")).with_(num_kv_heads=2)
model = build_model(cfg)
pspec = model.param_specs()
ospec = opt_state_specs(pspec, cfg)
params = init_params(pspec, jax.random.key(0), cfg.param_dtype)
opt = init_params(ospec, jax.random.key(1), cfg.optstate_dtype)
data = SyntheticLM(DataConfig(vocab_size=cfg.vocab_size, seq_len=32,
                              global_batch=8))
batch = jax.tree.map(jnp.asarray, data.batch(0))
step = make_train_step(model, TrainConfig())
_, _, m_ref = jax.jit(step)(params, opt, batch)

mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
rules = param_rules(fsdp=True, multi_pod=True)
p_sh = make_shardings(pspec, mesh, rules)
o_sh = make_shardings(ospec, mesh, rules)
with mesh, activation_sharding(mesh, rules):
    p2 = jax.device_put(params, p_sh)
    o2 = jax.device_put(opt, o_sh)
    _, _, m_sh = jax.jit(step)(p2, o2, batch)
print("REF", float(m_ref["loss"]), "SHARDED", float(m_sh["loss"]))
assert abs(float(m_ref["loss"]) - float(m_sh["loss"])) < 5e-3
print("OK")
""")
    assert "OK" in out


def test_pipeline_forward_matches_sequential():
    out = run_py("""
import jax, jax.numpy as jnp, numpy as np
from repro.runtime.pipeline import pipeline_forward
S, M, B, D = 4, 6, 3, 16
rng = np.random.default_rng(0)
W = jnp.asarray(rng.normal(size=(S, D, D)) * 0.3, jnp.float32)
b = jnp.asarray(rng.normal(size=(S, D)) * 0.1, jnp.float32)
x = jnp.asarray(rng.normal(size=(M, B, D)), jnp.float32)
def stage_fn(p, h):
    return jnp.tanh(h @ p["w"] + p["b"])
params = {"w": W, "b": b}
# sequential reference
ref = x
for s in range(S):
    ref = jnp.tanh(ref @ W[s] + b[s])
mesh = jax.make_mesh((S,), ("stage",))
got = pipeline_forward(stage_fn, params, x, mesh)
np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=2e-5,
                           atol=2e-5)
print("OK")
""", devices=4)
    assert "OK" in out


def test_elastic_restore_across_mesh_sizes(tmp_path):
    """Checkpoint written under an 8-device mesh restores (resharded) under
    a 4-device mesh — elastic scaling."""
    out = run_py(f"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as PS
from repro.runtime.checkpoint import Checkpointer
mesh8 = jax.make_mesh((8,), ("data",))
x = jnp.arange(64.0).reshape(8, 8)
xs = jax.device_put(x, NamedSharding(mesh8, PS("data")))
ck = Checkpointer("{tmp_path}", async_save=False)
ck.save(5, {{"x": xs}})
# restore onto a DIFFERENT (4-device) mesh
devs = jax.devices()[:4]
mesh4 = jax.sharding.Mesh(np.array(devs), ("data",))
like = jax.ShapeDtypeStruct((8, 8), jnp.float32,
                            sharding=NamedSharding(mesh4, PS("data")))
restored, step = ck.restore({{"x": like}})
assert step == 5
np.testing.assert_array_equal(np.asarray(restored["x"]), np.asarray(x))
assert len(restored["x"].sharding.device_set) == 4
print("OK")
""")
    assert "OK" in out
