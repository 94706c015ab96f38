"""Pipeline parallelism + MoE dispatch-mode tests."""

import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_reduced
from repro.models.lm import LM
from tests.test_sharding_multidev import run_sub


class TestMoEDispatchModes:
    def _loss(self, cfg, toks):
        model = LM(cfg)
        params = model.init_params(jax.random.PRNGKey(0))
        loss, _ = model.train_loss(params, {"tokens": toks, "labels": toks},
                                   remat=False)
        return float(loss), model, params

    @pytest.mark.parametrize("arch", ["qwen2-moe-a2.7b", "deepseek-v2-lite-16b"])
    def test_local_equals_global_when_no_drops(self, arch):
        cfg = get_reduced(arch)   # capacity_factor 8 -> no drops
        toks = jax.random.randint(jax.random.PRNGKey(1), (2, 24), 0, cfg.vocab)
        l_global, _, _ = self._loss(
            cfg.with_overrides(moe=dataclasses.replace(cfg.moe,
                                                       dispatch="global")),
            toks)
        l_local, _, _ = self._loss(
            cfg.with_overrides(moe=dataclasses.replace(cfg.moe,
                                                       dispatch="local")),
            toks)
        assert abs(l_global - l_local) < 1e-5

    def test_local_dispatch_grads_finite(self):
        cfg = get_reduced("qwen2-moe-a2.7b")
        cfg = cfg.with_overrides(moe=dataclasses.replace(cfg.moe,
                                                         dispatch="local"))
        model = LM(cfg)
        params = model.init_params(jax.random.PRNGKey(0))
        toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab)
        g = jax.grad(lambda p: model.train_loss(
            p, {"tokens": toks, "labels": toks})[0])(params)
        gn = jnp.sqrt(sum(jnp.sum(x ** 2) for x in jax.tree.leaves(g)))
        assert jnp.isfinite(gn) and gn > 0


class TestPipeline:
    def test_pipeline_matches_sequential(self):
        run_sub("""
import numpy as np, jax, jax.numpy as jnp
from repro.runtime.pipeline import pipeline_apply
mesh = jax.make_mesh((4, 2), ("pod", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
rng = np.random.default_rng(0)
n_stages, n_micro, mb, d = 4, 6, 2, 16
W = jnp.asarray(rng.standard_normal((n_stages, d, d)) * 0.3, jnp.float32)
x = jnp.asarray(rng.standard_normal((n_micro, mb, d)), jnp.float32)
stage = lambda w, h: jnp.tanh(h @ w)
with mesh:
    y = pipeline_apply(mesh, stage, W, x, axis="pod")
ref = x
for s in range(n_stages):
    ref = jnp.tanh(ref @ W[s])
assert float(jnp.abs(y - ref).max()) < 1e-5
print("OK")
""")

    def test_seq_shard_decode_matches_replicated(self):
        """The §Perf seq-shard cache fallback must be numerics-neutral."""
        run_sub("""
import dataclasses
import numpy as np, jax, jax.numpy as jnp
from repro.configs import get_reduced
from repro.configs.base import ShapeCfg
from repro.models.lm import LM
from repro.runtime.serve import make_decode_step
mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
cfg = get_reduced("stablelm-12b")
model = LM(cfg)
params = model.init_params(jax.random.PRNGKey(0))
toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab)
_, caches, lengths = model.prefill(params, {"tokens": toks}, cache_cap=32)
new_tok = jnp.asarray([3, 5], jnp.int32)
outs = {}
for label, fb in [("replicated", False), ("seqshard", True)]:
    with mesh:
        step = make_decode_step(model, cfg, mesh=mesh, batch=2, cache_cap=32,
                                seq_shard_fallback=fb, donate_cache=False)
        logits, _ = step(params, new_tok, caches, lengths)
    outs[label] = np.asarray(logits)
err = np.abs(outs["replicated"] - outs["seqshard"]).max()
assert err < 1e-4, err
print("OK", err)
""")
