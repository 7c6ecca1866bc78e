"""The ``ssm_step`` kernel (``ops/pallas/ssm.py``) in interpret mode on the
CPU against its einsum twin: the numbers agree, a slot that does not decode
keeps its state bit for bit (and is not on the kernel's list of slots to
move), other planes are not touched, and garbage in an idle slot's operands
reaches nothing."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops.pallas import ssm

L, S, N, D = 3, 6, 16, 256


def operands(seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    return (jax.random.normal(k[0], (L, S, N, D)), jax.random.normal(k[1], (S, D)),
            jax.random.uniform(k[2], (S, D), minval=0.5, maxval=1.0),
            jax.random.normal(k[3], (S, N)), jax.random.normal(k[4], (S, N)))


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
