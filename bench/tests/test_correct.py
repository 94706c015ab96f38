"""``correct`` on the CPU at a tiny size: a sound run passes, every fault
the serving path can have fails, and the control computed one precision
step lower reads above the fixture's limit.  The chip readings behind the
real cells' limits come from ``bench/control.py``."""

import jax.numpy as jnp
import numpy as np
import pytest

import run
from test_harness import fixture_layout


def result(breaker=None, seed=2**33 + 7, workload="tiny.chat"):
    import jax
    cell = fixture_layout().cell(workload)
    return run.run_cell(cell, seed=seed, seconds=1.5, traced=False,
                        devices=jax.devices(),
                        peaks={"matmul_flops_per_s": 1e12,
                               "hbm_bytes_per_s": 1e11},
                        breaker=breaker)


def wrap_call(session, fault):
    """Plant ``fault(stepper, fn, args, call)`` under the stepper's
    Program call, below the benchmark's own wrappers."""
    st = session.engine.stepper
    call = st._call

    def broken(fn, tokens, start, n_new, *extra):
        return fault(st, fn, (tokens, start, n_new, *extra), call)
    st._call = broken


def state_unchanged(session):
    """A step that returns its state (the KV pages) unchanged."""
    def fault(st, fn, args, call):
        before = {k: jnp.copy(v) for k, v in st.caches.items()}
        out = call(fn, *args)
        st.caches = before
        return out
    wrap_call(session, fault)


def half_batch(session):
    """Half of the batch left out: the upper slots' rows never reach the
    Program, and their logits are whatever it computes without them."""
    def fault(st, fn, args, call):
        tokens, start, n_new, *extra = args
        n_new = np.array(n_new)
        n_new[st.n_slots // 2:] = 0
        return call(fn, tokens, start, n_new, *extra)
    wrap_call(session, fault)


def token_altered(session):
    """A token altered where it is produced: every fourth decode call
    moves each live slot's best logit to another token."""
    count = [0]

    def fault(st, fn, args, call):
        out = call(fn, *args)
        live = np.flatnonzero(args[2])
        if out.ndim == 2 and len(live):
            count[0] += 1
            if count[0] % 4 == 0:
                out = out.copy()
                for s in live:
                    out[s, (int(np.argmax(out[s])) + 1) % out.shape[1]] = \
                        out[s].max() + 1.0
        return out
    wrap_call(session, fault)


@pytest.mark.parametrize("workload", ["tiny.chat", "tiny.decode"])
def test_a_sound_run_is_correct(workload):
    res = result(workload=workload)
    assert res["correct"], res["checks"]
    assert res["checks"]["served_logit_gap4"]["value"] <= 1e-18
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("breaker", [state_unchanged, half_batch,
                                     token_altered])
def test_a_fault_under_the_timed_path_is_not_correct(breaker):
    # the saturated cell keeps every slot busy, so every slot's requests
    # are among those the check can sample
    res = result(breaker, workload="tiny.decode")
    assert not res["correct"], res["checks"]


def test_the_control_fails_the_limit():
    """The control, one precision step lower, put in the program's place
    on the same sample, goes through the same check and fails it."""
    import jax
    cell = fixture_layout().cell("tiny.decode")
    session = run.build(cell, 11)
    win = run.serve(session, seconds=2.0, traced=False)
    program = run.check(cell, session.params, win, 11)
    control = run.check(cell, session.params, win, 11, control=True)
    assert run.passed(program), program
    assert not run.passed(control), control
    assert (program["served_logit_gap4"]["value"]
            <= cell.checks["served_logit_gap4"]
            < control["served_logit_gap4"]["value"])
    assert jax.devices()[0].platform == "cpu"
