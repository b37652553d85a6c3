"""``ops/ssm.py``: the chunked scan against the plain recurrence (its
initial state, final state, the state at a position, lanes of unequal
length), and the one-token step as a Pallas kernel in interpret mode
against its einsum path (live mask, in place, groups), and the count
of slot states its fetch plan reads."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from edl_tpu.ops import ssm

B, L, H, P, N = 3, 21, 4, 16, 8


def inputs(groups=1, seed=0, length=L):
    k = jax.random.split(jax.random.key(seed), 6)
    x = jax.random.normal(k[0], (B, length, H, P))
    dt = jax.nn.softplus(jax.random.normal(k[1], (B, length, H))) * 0.3
    A = -jnp.exp(jax.random.normal(k[2], (H,)))
    Bm = jax.random.normal(k[3], (B, length, groups, N))
    Cm = jax.random.normal(k[4], (B, length, groups, N))
    S0 = jax.random.normal(k[5], (B, H, P, N))
    return x, dt, A, Bm, Cm, S0


def close(got, want, tol=2e-5):
    got, want = np.asarray(got), np.asarray(want)
    assert got.shape == want.shape
    assert np.abs(got - want).max() <= tol * max(1.0, np.abs(want).max())


@pytest.mark.parametrize("groups", [1, 2])
@pytest.mark.parametrize("chunk", [7, 8, 32])
def test_scan_from_a_state_equals_the_recurrence(chunk, groups):
    """Chunks that divide the length, do not, and exceed it; outputs
    and the final state, FROM a state that is not zero."""
    x, dt, A, Bm, Cm, S0 = inputs(groups)
    y0, f0 = ssm.ssm_recurrence(x, dt, A, Bm, Cm, S0)
    y, f, snap = ssm.ssd_scan(x, dt, A, Bm, Cm, S0, chunk=chunk)
    assert snap is None
    close(y, y0)
    close(f, f0)


@pytest.mark.parametrize("at", [0, 5, 8, 16, 21])
def test_state_at_a_position_is_the_recurrence_stopped_there(at):
    """0 is the initial state, 8 and 16 chunk edges, 21 the end."""
    x, dt, A, Bm, Cm, S0 = inputs()
    _, _, snap = ssm.ssd_scan(x, dt, A, Bm, Cm, S0, chunk=8,
                              snap_at=jnp.full((B,), at))
    want = (S0 if at == 0 else ssm.ssm_recurrence(
        x[:, :at], dt[:, :at], A, Bm[:, :at], Cm[:, :at], S0)[1])
    close(snap, want)


def test_lanes_of_unequal_length_stop_at_their_own_end():
    """Positions past a lane's length move no state: each lane's final
    state, outputs and snapshot are its unpadded run's."""
    x, dt, A, Bm, Cm, S0 = inputs()
    lens, ats = [21, 13, 1], [16, 13, 0]
    y, f, snap = ssm.ssd_scan(x, dt, A, Bm, Cm, S0, chunk=8,
                              lengths=jnp.asarray(lens),
                              snap_at=jnp.asarray(ats))
    for b, (n, at) in enumerate(zip(lens, ats)):
        def run(upto):
            return ssm.ssm_recurrence(
                x[b:b + 1, :upto], dt[b:b + 1, :upto], A, Bm[b:b + 1, :upto],
                Cm[b:b + 1, :upto], S0[b:b + 1])
        yb, fb = run(n)
        close(y[b, :n], yb[0])
        close(f[b], fb[0])
        close(snap[b], S0[b] if at == 0 else run(at)[1][0])


LIVE = [[False, True, False], [True, True, True], [False, False, False],
        [True, False, False], [False, False, True]]


@pytest.mark.parametrize("groups", [1, 2])
@pytest.mark.parametrize("live", LIVE, ids=lambda v: "".join(
    "x" if b else "-" for b in v))
def test_kernel_step_matches_the_einsum_step(live, groups):
    """Free slots first, last, between, all and none: live slots'
    states and outputs are the einsum path's, free slots keep theirs
    and return zeros."""
    x, dt, A, Bm, Cm, S0 = inputs(groups, seed=1, length=1)
    args = (x[:, 0], dt[:, 0], A, Bm[:, 0], Cm[:, 0], jnp.asarray(live))
    y0, s0 = ssm.ssm_step_reference(S0, *args)
    y, s = jax.jit(lambda S, *a: ssm.ssm_step(S, *a, interpret=True),
                   donate_argnums=(0,))(S0 + 0.0, *args)
    close(y, y0)
    close(s, s0)
    for b, on in enumerate(live):
        if not on:
            np.testing.assert_array_equal(np.asarray(s[b]), np.asarray(S0[b]))
            assert float(jnp.abs(y[b]).max()) == 0.0


@pytest.mark.parametrize("live", LIVE, ids=lambda v: "".join(
    "x" if b else "-" for b in v))
def test_slots_fetched_counts_the_plan_the_kernel_runs_under(live):
    """The engine's ``ssm_state_steps_run`` is this count: the live
    slots while a free slot names the block already in VMEM (one block
    of four when no slot is live: the lead block is handed through)."""
    got = float(ssm.slots_fetched(jnp.asarray(live), 128, 64, 128))
    assert got == (sum(live) if any(live) else 0.25)


def test_slots_fetched_sees_a_plan_that_fetches_free_slots(monkeypatch):
    """Were the plan to name every slot's own blocks, the counter would
    say so: it is derived from what the index maps read, not from which
    path ran."""
    def every_slot(lengths, tk):
        n = lengths.shape[0]
        return (jnp.arange(n, dtype=jnp.int32), jnp.zeros((n,), jnp.int32),
                jnp.full((n,), 3, jnp.int32))
    monkeypatch.setattr(ssm, "_fetch_plan", every_slot)
    live = jnp.asarray([False, True, False])
    assert float(ssm.slots_fetched(live, 128, 64, 128)) == 3.0


def test_a_step_is_one_token_of_the_scan():
    x, dt, A, Bm, Cm, S0 = inputs(length=1)
    y, s = ssm.ssm_step_reference(S0, x[:, 0], dt[:, 0], A, Bm[:, 0],
                                  Cm[:, 0])
    y0, f0, _ = ssm.ssd_scan(x, dt, A, Bm, Cm, S0, chunk=8)
    close(y, y0[:, 0])
    close(s, f0)


def test_step_block_divides_a_groups_heads_within_the_budget():
    assert ssm.step_block(128, 64, 128) == 32       # 1 MiB of float32
    assert ssm.step_block(128, 64, 128, G=8) == 16  # one group's heads
    assert ssm.step_block(4, 16, 8) == 4


def test_dispatch_rule_is_length_mesh_and_backend_only(monkeypatch):
    assert not ssm.applies(1, None)                 # this CPU
    monkeypatch.setattr(ssm, "_on_tpu", lambda: True)
    assert ssm.applies(1, None)
    assert not ssm.applies(2, None) and not ssm.applies(1, object())
