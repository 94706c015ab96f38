"""Multi-device sharding tests — run in SUBPROCESSES with
--xla_force_host_platform_device_count (the main test process keeps the
real single CPU device, per the dry-run isolation rule).

Covers: TP/DP train-step numerics vs single-device, tree-decode
(sequence-parallel) vs dense decode, compressed DP all-reduce, ring
all-gather matmul, and the dry-run cell machinery on a small mesh.
"""

from conftest import run_sub


PREAMBLE = """
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
assert len(jax.devices()) == 8, jax.devices()
mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
"""


def test_sharded_train_step_matches_single_device():
    run_sub(PREAMBLE + """
from repro.configs import get_reduced
from repro.models.lm import LM
from repro.optim import adamw
from repro.optim.adamw import AdamWConfig
from repro.runtime.train import make_train_step, train_state_shardings
from repro.data import SyntheticLM

cfg = get_reduced("stablelm-12b")
model = LM(cfg)
opt_cfg = AdamWConfig(lr=1e-3)
params = model.init_params(jax.random.PRNGKey(0))
state = adamw.init(params, opt_cfg)
ds = SyntheticLM(vocab=cfg.vocab, seq_len=16, batch=4, seed=2)
batch = {k: jnp.asarray(v) for k, v in ds.batch_at(0).items()}

# single-device reference
step1 = make_train_step(model, cfg, opt_cfg, donate=False)
p1, s1, m1 = step1(params, state, batch)

# sharded
with mesh:
    stepN = make_train_step(model, cfg, opt_cfg, mesh=mesh,
                            batch_example=batch, donate=False)
    pN, sN, mN = stepN(params, state, batch)
assert abs(float(m1["loss"]) - float(mN["loss"])) < 1e-4, (m1["loss"], mN["loss"])
err = max(float(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)).max())
          for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(pN)))
assert err < 1e-4, err
# moments actually sharded (ZeRO-1): some leaf has a non-trivial sharding
sharded = [x for x in jax.tree.leaves(sN["mu"])
           if not x.sharding.is_fully_replicated]
assert sharded, "no optimizer moment is sharded"
print("OK train", err)
""")


def test_tree_decode_matches_dense():
    run_sub(PREAMBLE + """
from repro.sharding.collectives import tree_decode_attention
from repro.kernels.ref import decode_attention_ref
rng = np.random.default_rng(0)
b, skv, hq, hkv, d = 2, 64, 4, 2, 16
q = jnp.asarray(rng.standard_normal((b, hq, d)), jnp.float32)
k = jnp.asarray(rng.standard_normal((b, skv, hkv, d)), jnp.float32)
v = jnp.asarray(rng.standard_normal((b, skv, hkv, d)), jnp.float32)
lens = jnp.asarray([40, 64], jnp.int32)
ref = decode_attention_ref(q, k, v, lens)
with mesh:
    out = tree_decode_attention(mesh, q, k, v, lens, axis="data", backend="ref")
err = float(jnp.abs(out - ref).max())
assert err < 1e-5, err
print("OK tree-decode", err)
""")


def test_compressed_psum_and_ring_matmul():
    run_sub(PREAMBLE + """
from repro.optim.compress import compressed_psum_mean
from repro.sharding.collectives import ring_allgather_matmul
rng = np.random.default_rng(1)
grads = {"a": jnp.asarray(rng.standard_normal((8, 16)), jnp.float32),
         "b": jnp.asarray(rng.standard_normal((4,)), jnp.float32)}
errs = jax.tree.map(jnp.zeros_like, grads)
with mesh:
    fn = compressed_psum_mean(mesh, axis="data")
    mean, new_err = fn(grads, errs)
# all shards identical input => mean == dequantised input, err small
for k in grads:
    rel = float(jnp.abs(mean[k] - grads[k]).max() / jnp.abs(grads[k]).max())
    assert rel < 0.02, (k, rel)

x = jnp.asarray(rng.standard_normal((16, 8)), jnp.float32)
w = jnp.asarray(rng.standard_normal((8, 12)), jnp.float32)
with mesh:
    y = ring_allgather_matmul(mesh, x, w, axis="model")
err = float(jnp.abs(y - x @ w).max())
assert err < 1e-4, err
print("OK compress+ring", err)
""")


def test_dryrun_cell_machinery_small_mesh():
    """build_cell -> lower -> compile -> cost/memory/collective parse, on a
    (2,4) mesh with reduced configs — the dry-run pipeline end-to-end."""
    run_sub(PREAMBLE + """
import dataclasses
from repro.configs import get_reduced
from repro.configs.base import ShapeCfg
from repro.launch.cells import build_cell
from repro.tools.roofline import analyze, collective_bytes, model_flops_for

for name in ["stablelm-12b", "qwen2-moe-a2.7b", "mamba2-370m"]:
    cfg = get_reduced(name)
    cfg = dataclasses.replace(cfg, shapes=(ShapeCfg("t", "train", 32, 4),))
    with mesh:
        cell = build_cell(name, "t", mesh, cfg=cfg)
        co = cell.step.lower(*cell.args).compile()
        cost = co.cost_analysis()
        hlo = co.as_text()
    wire, per_type, counts = collective_bytes(hlo, 8)
    assert cost.get("flops", 0) > 0
    assert wire > 0, "expected collectives in a sharded train step"
    rep = analyze(cell.name, "test", 8, cost, hlo,
                  model_flops=model_flops_for(cfg, "train", 32, 4))
    assert rep.bottleneck in ("compute", "memory", "collective")
    print("OK", name, rep.bottleneck, counts)
""")


def test_elastic_reshard_across_meshes():
    """Save on a (2,4) mesh, restore onto (4,2) and (8,1) — values equal."""
    run_sub(PREAMBLE + """
import tempfile, os
from repro.checkpoint import io as ckpt_io
rng = np.random.default_rng(2)
state = {"w": jnp.asarray(rng.standard_normal((8, 16)), jnp.float32)}
sh1 = {"w": NamedSharding(mesh, P("data", "model"))}
state1 = jax.device_put(state, sh1)
with tempfile.TemporaryDirectory() as td:
    ckpt_io.save(td, 1, state1)
    for shape, axes in [((4, 2), ("data", "model")), ((8, 1), ("data", "model"))]:
        mesh2 = jax.make_mesh(shape, axes,
                              axis_types=(jax.sharding.AxisType.Auto,) * 2)
        sh2 = {"w": NamedSharding(mesh2, P("data", "model"))}
        target = {"w": jax.ShapeDtypeStruct((8, 16), jnp.float32)}
        r = ckpt_io.restore(td, target, shardings=sh2)
        np.testing.assert_array_equal(np.asarray(r["w"]), np.asarray(state["w"]))
        assert r["w"].sharding == sh2["w"]
print("OK elastic")
""")
