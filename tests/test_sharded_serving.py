"""Tensor-parallel serving exactness — the bar for ``compile(mesh=...)``
plus the engine's multi-device path.

Everything runs in SUBPROCESSES with forced host devices (the main test
process keeps the real single CPU device, per the dry-run isolation
rule).

The bar is strict token IDENTITY, not closeness: the TP=2 engine must
emit exactly the single-device engine's tokens for dense, paged-fp32
and paged-int8 Programs, cold and on prefix hits, through GQA fallback
and through self-heal crash recovery.  That works because the ``tp``
attention backends never split a contraction: heads are computed whole
per device and the only collective is an exact output all-gather
(row-parallel weights stay replicated — see
``repro.sharding.specs.serving_value_role``).
"""

from conftest import run_sub

PREAMBLE = """
import numpy as np, jax
import repro  # registers every op/backend
from repro.models.graph_lm import GraphLMConfig
from repro.runtime.engine import EngineRequest, build_lm_serving

assert len(jax.devices()) == 8, jax.devices()
TINY = GraphLMConfig(vocab=61, d_model=32, n_layers=2, n_heads=4,
                     n_kv_heads=2, d_ff=64)

def reqs(seed, n=5, vocab=61):
    rng = np.random.default_rng(seed)
    return [EngineRequest(uid=i,
                prompt=rng.integers(0, vocab,
                                    size=int(rng.integers(1, 13))).astype(np.int32),
                max_new_tokens=int(rng.integers(1, 7)))
            for i in range(n)]

def drive(engine, rs):
    for r in rs:
        assert engine.submit(r), r.dropped
    engine.run(max_ticks=engine.tick + 4000)
    for r in rs:
        assert r.done and r.dropped is None, (r.uid, r.dropped)
    return [tuple(r.out_tokens) for r in rs]

def assert_tp_attention(engine):
    asn = engine.stepper.decode_program.assignment
    tp_nodes = [n for n, b in asn.items() if b == "tp"]
    assert tp_nodes, ("tp backend never selected", asn)
"""


def test_tp_backends_bitwise_equal_xla():
    """Op level: the shard_map tp backends are bitwise-identical to their
    single-device xla lowerings on a 2-device ("model",) mesh."""
    run_sub(PREAMBLE + """
from repro.kernels.serving_ops import (chunk_attention,
                                       paged_decode_attention_q,
                                       serving_mesh)
from repro.launch.mesh import make_serving_mesh

rng = np.random.default_rng(0)
q = rng.standard_normal((2, 4, 4, 8)).astype(np.float32)
k = rng.standard_normal((2, 4, 2, 8)).astype(np.float32)
v = rng.standard_normal((2, 4, 2, 8)).astype(np.float32)
start = np.zeros((2,), np.int32)
want = np.asarray(chunk_attention(q, k, v, start, backend="xla"))
mesh = make_serving_mesh(2)
with serving_mesh(mesh):
    got = np.asarray(chunk_attention(q, k, v, start, backend="tp"))
np.testing.assert_array_equal(got, want)

qd = rng.standard_normal((2, 4, 8)).astype(np.float32)
pk = rng.integers(-127, 128, size=(6, 4, 2, 8)).astype(np.int8)
pv = rng.integers(-127, 128, size=(6, 4, 2, 8)).astype(np.int8)
ks = rng.uniform(0.01, 0.1, size=(6, 2)).astype(np.float32)
vs = rng.uniform(0.01, 0.1, size=(6, 2)).astype(np.float32)
tables = np.array([[0, 2], [1, 3]], np.int32)
lengths = np.array([7, 5], np.int32)
want = np.asarray(paged_decode_attention_q(qd, pk, ks, pv, vs, tables,
                                           lengths, backend="xla"))
with serving_mesh(mesh):
    got = np.asarray(paged_decode_attention_q(qd, pk, ks, pv, vs, tables,
                                              lengths, backend="tp"))
np.testing.assert_array_equal(got, want)
print("OK")
""")


def test_tp_engine_dense_token_identical():
    """Dense caches: TP=2 engine == TP=None engine == unbatched reference;
    and a GQA-small config (Hk=1, tp=2) replicates KV and stays exact."""
    run_sub(PREAMBLE + """
kw = dict(n_slots=3, chunk=4, cache_cap=48)
e1, ref1 = build_lm_serving(TINY, **kw)
base = drive(e1, reqs(7))
e2, ref2 = build_lm_serving(TINY, **kw, tp=2)
assert drive(e2, reqs(7)) == base
assert_tp_attention(e2)
for r, toks in zip(reqs(7), base):
    assert list(toks) == ref2.generate(r.prompt, r.max_new_tokens)

# GQA-small fallback: Hk=1 does not divide tp=2 -> KV replicated, still exact
TG = GraphLMConfig(vocab=61, d_model=32, n_layers=1, n_heads=4,
                   n_kv_heads=1, d_ff=64)
eg, refg = build_lm_serving(TG, n_slots=2, chunk=4, cache_cap=32, tp=2)
for r, toks in zip(reqs(3, n=3), drive(eg, reqs(3, n=3))):
    assert list(toks) == refg.generate(r.prompt, r.max_new_tokens)
print("OK")
""")


def test_tp_engine_paged_fp32_cold_and_prefix_hit():
    run_sub(PREAMBLE + """
kw = dict(n_slots=3, chunk=4, cache_cap=48, paged=True, page_size=8)
e1, _ = build_lm_serving(TINY, **kw)
base = drive(e1, reqs(8))
e2, ref = build_lm_serving(TINY, **kw, tp=2)
assert drive(e2, reqs(8)) == base
assert_tp_attention(e2)

rng = np.random.default_rng(12)
prefix = rng.integers(0, 61, size=24).astype(np.int32)
cold = EngineRequest(uid=100, prompt=np.concatenate(
    [prefix, rng.integers(0, 61, size=3).astype(np.int32)]), max_new_tokens=5)
assert e2.submit(cold); e2.run(max_ticks=e2.tick + 500)
assert cold.out_tokens == ref.generate(cold.prompt, 5)
hits0 = e2.stepper.pool.hit_tokens
warm = EngineRequest(uid=101, prompt=np.concatenate(
    [prefix, rng.integers(0, 61, size=2).astype(np.int32)]), max_new_tokens=5)
assert e2.submit(warm); e2.run(max_ticks=e2.tick + 500)
assert e2.stepper.pool.hit_tokens - hits0 >= 24, "sharded pages never hit"
assert warm.out_tokens == ref.generate(warm.prompt, 5)
e2.stepper.pool.check_integrity()
print("OK")
""")


def test_tp_engine_paged_int8_cold_and_prefix_hit():
    """int8 KV pages + sharded scale sidecars stay token-exact vs the
    dense fp32 reference, cold and on prefix hits."""
    run_sub(PREAMBLE + """
e, ref = build_lm_serving(TINY, n_slots=3, chunk=4, cache_cap=48,
                          paged=True, page_size=8, kv_dtype="int8", tp=2)
assert e.stepper.pool.kv_dtype == "int8"
rng = np.random.default_rng(21)
rs = reqs(21, n=7)
for r, toks in zip(rs, drive(e, rs)):
    assert list(toks) == ref.generate(r.prompt, r.max_new_tokens)
assert_tp_attention(e)

prefix = rng.integers(0, 61, size=24).astype(np.int32)
cold = EngineRequest(uid=100, prompt=np.concatenate(
    [prefix, rng.integers(0, 61, size=3).astype(np.int32)]), max_new_tokens=5)
assert e.submit(cold); e.run(max_ticks=e.tick + 500)
assert cold.out_tokens == ref.generate(cold.prompt, 5)
hits0 = e.stepper.pool.hit_tokens
warm = EngineRequest(uid=101, prompt=np.concatenate(
    [prefix, rng.integers(0, 61, size=2).astype(np.int32)]), max_new_tokens=5)
assert e.submit(warm); e.run(max_ticks=e.tick + 500)
assert e.stepper.pool.hit_tokens - hits0 >= 24
assert warm.out_tokens == ref.generate(warm.prompt, 5)
print("OK")
""")


def test_tp_engine_self_heal_recovery_token_identical():
    """Crash recovery under TP: the checkpoint's id-level pool snapshot
    stays in lockstep with the head-sharded device pages."""
    run_sub(PREAMBLE + """
rng = np.random.default_rng(42)
head = rng.integers(0, 61, size=6).astype(np.int32)
prompts = []
for i in range(6):
    tail = rng.integers(0, 61, size=int(rng.integers(2, 9))).astype(np.int32)
    prompts.append(np.concatenate([head, tail]) if i % 2 else tail)

def run(tp, inject):
    engine, _ = build_lm_serving(TINY, n_slots=3, chunk=4, cache_cap=48,
                                 paged=True, self_heal=True, tp=tp)
    rs = []
    for i, p in enumerate(prompts):
        r = EngineRequest(uid=i, prompt=p, max_new_tokens=6)
        assert engine.submit(r); rs.append(r)
    if inject:
        calls = [0]
        for phase in ("decode", "prefill"):
            orig = getattr(engine.stepper, phase)
            def wrapped(*args, _orig=orig):
                calls[0] += 1
                if calls[0] in (3, 7, 11):
                    raise RuntimeError("injected fault")
                return _orig(*args)
            setattr(engine.stepper, phase, wrapped)
    engine.run()
    assert all(r.done and r.dropped is None for r in rs)
    if inject:
        assert engine.metrics.n_recoveries >= 1
    engine.stepper.pool.check_integrity()
    return [tuple(r.out_tokens) for r in rs]

base = run(None, False)
assert run(2, False) == base, "tp clean run differs"
assert run(2, True) == base, "tp recovery run differs"
print("OK")
""")
