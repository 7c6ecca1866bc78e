"""The ``ssm_step`` kernel (``ops/pallas/ssm.py``) in interpret mode on the
CPU against its einsum twin: the numbers agree, a slot that does not decode
keeps its state bit for bit (and is not on the kernel's list of slots to
move), other planes are not touched, and garbage in an idle slot's operands
reaches nothing; with ``B`` and ``C`` in groups a channel reads its own group's,
at a small size and at Nemotron-H's ``[128, 8192]`` in 8 groups, and the step
walks the plain recurrence of ``hybrid_ssm_lm.ssm_scan``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops.pallas import ssm

L, S, N, D = 3, 6, 16, 256


def operands(seed=0, groups=1, shape=(L, S, N, D)):
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    _, s, n, d = shape
    return (jax.random.normal(k[0], shape), jax.random.normal(k[1], (s, d)),
            jax.random.uniform(k[2], (s, d), minval=0.5, maxval=1.0),
            jax.random.normal(k[3], (s, groups, n)), jax.random.normal(k[4], (s, groups, n)))


@pytest.mark.parametrize("active", [[1, 0, 1, 1, 0, 1], [0, 0, 0, 0, 0, 0], [1, 1, 1, 1, 1, 1],
                                    [0, 0, 0, 0, 0, 1], [1, 0, 0, 0, 0, 0]],
                         ids=["ragged", "none", "all", "last", "first"])
def test_the_kernel_is_the_einsum_form_and_leaves_idle_slots_alone(active):
    state, xdt, decay, b, c = operands()
    on = jnp.asarray(active)
    y_k, s_k = ssm.ssm_step(state, xdt, decay, b, c, on, layer=1, interpret=True)
    y_x, s_x = ssm.ssm_step_xla(state, xdt, decay, b, c, on, layer=1)
    # float32 multiply-adds in another order: a few units in the last place
    np.testing.assert_allclose(y_k, y_x, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(s_k, s_x, rtol=1e-6, atol=1e-6)
    idle = np.asarray(active) == 0
    for got in (s_k, s_x):  # bit for bit, kernel and twin alike
        assert (np.asarray(got[1])[idle] == np.asarray(state[1])[idle]).all()
        assert (np.asarray(got[0]) == np.asarray(state[0])).all()
        assert (np.asarray(got[2]) == np.asarray(state[2])).all()
    assert not np.asarray(y_k)[idle].any() and not np.asarray(y_x)[idle].any()
    if idle.all():
        return
    # an active slot's state did change
    assert (np.asarray(s_k[1])[~idle] != np.asarray(state[1])[~idle]).any()


@pytest.mark.parametrize("groups, shape", [(2, (L, S, N, D)), (4, (L, S, N, D)),
                                           (8, (1, 2, 128, 8192))],
                         ids=["2_groups", "4_groups", "8_groups_at_128x8192"])
def test_with_groups_a_channel_reads_its_own_groups_b_and_c(groups, shape):
    """Kernel and twin against the recurrence spelled a channel at a time; the
    last case is Nemotron-H's state tile, chunks of 512 lanes in groups of 1024."""
    state, xdt, decay, b, c = operands(3, groups, shape)
    on = jnp.ones((shape[1],), jnp.int32).at[0].set(0 if shape[1] > 2 else 1)
    layer = shape[0] - 1
    y_k, s_k = ssm.ssm_step(state, xdt, decay, b, c, on, layer=layer, interpret=True)
    y_x, s_x = ssm.ssm_step_xla(state, xdt, decay, b, c, on, layer=layer)
    of = lambda v: jnp.repeat(jnp.swapaxes(v, 1, 2), shape[3] // groups, axis=-1)  # [S, N, D]
    live = (on != 0)[:, None, None]
    want = jnp.where(live, decay[:, None] * state[layer] + of(b) * xdt[:, None], state[layer])
    want_y = jnp.where(live[:, 0], jnp.sum(of(c) * want, axis=1), 0.0)
    for got_y, got_s in ((y_k, s_k), (y_x, s_x)):
        np.testing.assert_allclose(got_y, want_y, rtol=1e-5, atol=2e-5)
        np.testing.assert_allclose(got_s[layer], want, rtol=1e-6, atol=1e-6)
    # every channel reading group 0's would not pass
    wrong = jnp.sum(of(c[:, :1].repeat(groups, 1)) * want, axis=1)
    assert np.abs(np.asarray(wrong - want_y))[np.asarray(on) != 0].max() > 1.0


@pytest.mark.parametrize("groups", [1, 2])
def test_the_step_walks_the_scan_a_token_at_a_time(groups):
    from paddle_tpu.models import hybrid_ssm_lm as hm

    T, H, P, n = 9, 4, 32, 16
    rng = np.random.default_rng(5)
    f32 = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    x, b, c, h0 = f32(T, H * P), f32(T, groups, n), f32(T, groups, n), f32(n, H * P)
    dt = jnp.asarray(rng.uniform(0.01, 0.5, (T, H)), jnp.float32)
    a_neg = -jnp.exp(0.3 * f32(H))
    want_y, want_h = hm.ssm_scan(x, dt, a_neg, b, c, h0)
    state, ys = h0[None, None], []
    for t in range(T):
        y, state = ssm.ssm_step(state, (hm._by_channel(dt[t], P) * x[t])[None],
                                hm._by_channel(jnp.exp(dt[t] * a_neg), P)[None], b[t][None],
                                c[t][None], jnp.ones((1,), jnp.int32), layer=0, interpret=True)
        ys.append(y[0])
    np.testing.assert_allclose(jnp.stack(ys), want_y, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(state[0, 0], want_h, rtol=1e-5, atol=1e-5)


def test_the_list_names_the_active_slots_first_and_repeats_the_last():
    ids, n = ssm.active_list(jnp.asarray([0, 1, 0, 1, 1, 0]))
    assert int(n) == 3 and ids.tolist() == [1, 3, 4, 4, 4, 4]
    ids, n = ssm.active_list(jnp.zeros((4,), jnp.int32))
    # nobody decodes: one entry, slot 0, whose operands the step has made a no-op
    assert int(n) == 1 and ids.tolist() == [0, 0, 0, 0]
    ids, n = ssm.active_list(jnp.ones((3,), jnp.int32))
    assert int(n) == 3 and ids.tolist() == [0, 1, 2]


def test_garbage_in_an_idle_slots_operands_reaches_nothing():
    state, xdt, decay, b, c = operands(1)
    on = jnp.asarray([1, 0, 1, 0, 0, 1])
    nan = lambda x: x.at[1].set(jnp.nan).at[3].set(jnp.inf)
    y, new = ssm.ssm_step(state, nan(xdt), nan(decay), nan(b), nan(c), on, layer=0,
                          interpret=True)
    y0, new0 = ssm.ssm_step(state, xdt, decay, b, c, on, layer=0, interpret=True)
    assert np.isfinite(np.asarray(y)).all() and np.isfinite(np.asarray(new)).all()
    assert (np.asarray(y) == np.asarray(y0)).all() and (np.asarray(new) == np.asarray(new0)).all()
    # and with nobody active the entry the list still names is a no-op
    none = jnp.zeros((S,), jnp.int32)
    _, kept = ssm.ssm_step(state, nan(xdt), nan(decay), nan(b), nan(c), none, layer=2,
                           interpret=True)
    assert (np.asarray(kept) == np.asarray(state)).all()


def test_the_layer_may_be_traced_and_the_state_is_donated():
    state, xdt, decay, b, c = operands(2)
    on = jnp.asarray([1, 1, 0, 1, 0, 1])

    @jax.jit
    def every_layer(state):
        def one(i, carry):
            y, state = carry
            y_i, state = ssm.ssm_step(state, xdt, decay, b, c, on, layer=i, interpret=True)
            return y + y_i, state

        return jax.lax.fori_loop(0, L, one, (jnp.zeros((S, D)), state))

    y, new = every_layer(state)
    want_y, want = jnp.zeros((S, D)), state
    for i in range(L):
        y_i, want = ssm.ssm_step_xla(want, xdt, decay, b, c, on, layer=i)
        want_y = want_y + y_i
    np.testing.assert_allclose(y, want_y, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(new, want, rtol=1e-6, atol=1e-6)


def test_operands_that_do_not_match_the_state_are_refused_by_name():
    state, xdt, decay, b, c = operands()
    with pytest.raises(Exception, match="ssm_step: operands do not match state"):
        ssm.ssm_step(state, xdt[:, :8], decay, b, c, jnp.ones((S,), jnp.int32), layer=0,
                     interpret=True)
