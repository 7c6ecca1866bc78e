"""The decode step's attention kernel, ``paged_attend_step``, in interpret
mode on the CPU against ``_kv_core`` over the gathered context, and the rule
by which ``_paged_attend`` takes it or keeps the gather."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.models import latent_moe_lm, transformer_lm
from paddle_tpu.models.transformer_lm import _kv_core, _live_mask, _paged_attend
from paddle_tpu.ops.pallas import paged_attention
from paddle_tpu.ops.pallas.paged_attention import paged_attend_step
from paddle_tpu.parallel.mesh import tp_submesh

PAGE = 16


def _gathered(q, k_pages, v_pages, plane, tables, pos):
    """The XLA form: every slot's whole table gathered, then the mask."""
    S, P = tables.shape
    page_size = k_pages.shape[2]
    live = _live_mask(pos, P * page_size, None).reshape(S, 1, 1, -1, P * page_size)

    def gather(j):
        pg = (k_pages, v_pages)[j]
        return jnp.take(pg.reshape((-1,) + pg.shape[2:]),
                        plane * pg.shape[1] + tables, axis=0, mode="clip")

    return _kv_core(q, gather, live)


def _pages(planes, num_pages, row, dtype, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 2)
    return [jax.random.normal(k, (planes, num_pages, PAGE, row), jnp.float32).astype(dtype)
            for k in keys]


def _tables(pos, P, rng=None):
    """A table row a slot holding the pages its position needs, in the
    allocator's order or shuffled over the whole array; the rest scratch."""
    S = len(pos)
    ids = np.arange(1, 1 + S * P)
    if rng is not None:
        ids = rng.permutation(ids)
    tables = np.zeros((S, P), np.int32)
    for s, p in enumerate(pos):
        n = p // PAGE + 1
        tables[s, :n] = ids[s * P:s * P + n]
    return jnp.asarray(tables)


# slots' positions: an idle slot (position 0 on a scratch row), one ending on
# a page's last row, one on a page's first row, one in a page's middle across
# two steps of the kernel, and a full table
P_WIDE = 12
RAGGED = [0, 3 * PAGE - 1, 2 * PAGE, 9 * PAGE + 5, P_WIDE * PAGE - 1]

CASES = {
    # lm_big's row: float32 pages, 16 heads of 64
    "f32_dh64_x16": dict(H=16, H_kv=16, dh=64, dtype=jnp.float32, tol=2e-5),
    # ouro_2_6b's row: bfloat16 pages, 16 heads of 128
    "bf16_dh128_x16": dict(H=16, H_kv=16, dh=128, dtype=jnp.bfloat16, tol=2e-2),
    "grouped_query_f32": dict(H=8, H_kv=2, dh=64, dtype=jnp.float32, tol=2e-5),
    "grouped_query_bf16": dict(H=8, H_kv=4, dh=128, dtype=jnp.bfloat16, tol=2e-2),
    "plane_other_than_0": dict(H=4, H_kv=4, dh=64, dtype=jnp.float32, tol=2e-5, plane=2),
    "pages_out_of_order": dict(H=4, H_kv=4, dh=64, dtype=jnp.float32, tol=2e-5, shuffled=True),
    "one_slot_one_page": dict(H=4, H_kv=2, dh=64, dtype=jnp.float32, tol=2e-5, pos=[5]),
    "plane_traced_in_a_scan": dict(H=4, H_kv=4, dh=64, dtype=jnp.float32, tol=2e-5,
                                   scanned=True),
}


@pytest.mark.parametrize("case", CASES.values(), ids=CASES.keys())
def test_kernel_matches_the_gathered_attention(case):
    pos = np.asarray(case.get("pos", RAGGED), np.int32)
    S, H, dh = len(pos), case["H"], case["dh"]
    planes = 3
    k_pages, v_pages = _pages(planes, 1 + S * P_WIDE, case["H_kv"] * dh, case["dtype"])
    tables = _tables(pos, P_WIDE, np.random.RandomState(1) if case.get("shuffled") else None)
    q = jax.random.normal(jax.random.PRNGKey(7), (S, H, dh), jnp.float32)
    pos = jnp.asarray(pos)
    if case.get("scanned"):
        # the page arrays ride the carry and are written before each plane's
        # call, as the layers' scan of looped_lm has them
        def body(carry, plane):
            k, v = (pg.at[plane, tables[:, 0], 0].add(1.0) for pg in carry)
            return (k, v), (paged_attend_step(q, k, v, plane, tables, pos),
                            _gathered(q, k, v, plane, tables, pos))

        _, (got, want) = jax.jit(lambda k, v: jax.lax.scan(
            body, (k, v), jnp.arange(planes)))(k_pages, v_pages)
        assert np.abs(np.asarray(want[0] - want[1])).max() > 1e-3  # planes differ
    else:
        plane = case.get("plane", 0)
        got = paged_attend_step(q, k_pages, v_pages, plane, tables, pos)
        want = _gathered(q, k_pages, v_pages, plane, tables, pos)
    assert got.shape == want.shape and got.dtype == want.dtype == jnp.float32
    # an idle slot's output is ignored by the engine; here it is one row's values
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=case["tol"], rtol=0)


def test_a_row_past_the_position_is_not_attended():
    """Rows of a live page past ``pos`` and pages past it hold anything:
    the output must not move with them."""
    pos = np.asarray([PAGE + 3, 5], np.int32)
    k_pages, v_pages = _pages(1, 1 + 2 * P_WIDE, 4 * 64, jnp.float32)
    tables = _tables(pos, P_WIDE)
    tables = tables.at[:, 2:].set(tables[0, 0])  # dead entries point at a live page
    q = jax.random.normal(jax.random.PRNGKey(7), (2, 4, 64), jnp.float32)
    got = paged_attend_step(q, k_pages, v_pages, 0, tables, jnp.asarray(pos))
    rows = jnp.arange(PAGE)[None, :, None]
    dead = (rows > 3) & (jnp.arange(k_pages.shape[1])[:, None, None] == tables[0, 1])
    moved = [jnp.where(dead, 1e4, pg[0])[None] for pg in (k_pages, v_pages)]
    again = paged_attend_step(q, *moved, 0, tables, jnp.asarray(pos))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(again))


def test_the_layers_of_an_unrolled_step_trace_the_kernel_once():
    """The plane is an argument, not a constant of the traced body: twelve
    layers calling with twelve Python ints meet one trace and one lowering
    (``lm_big``'s engine spent 10 s of set-up on the other eleven)."""
    k_pages, v_pages = _pages(3, 1 + 2 * P_WIDE, 4 * 64, jnp.float32)
    pos = jnp.asarray([PAGE + 3, 5])
    tables = _tables(np.asarray(pos), P_WIDE)
    q = jax.random.normal(jax.random.PRNGKey(7), (2, 4, 64), jnp.float32)
    text = jax.jit(lambda k, v: sum(
        paged_attend_step(q, k, v, plane, tables, pos) for plane in range(3))
    ).lower(k_pages, v_pages).as_text()
    assert text.count("call @_attend_step") == 3 and text.count("func.func private @_attend_step") == 1


class _Spy:
    def __init__(self):
        self.calls = 0

    def __call__(self, q, k_pages, v_pages, plane, tables, pos):
        self.calls += 1
        return jnp.zeros(q.shape, jnp.float32)


def _attend_once(pos, tables, core=None, rows=(4 * 64,) * 2, q_shape=None, window=None):
    """One call of ``_paged_attend``'s ``attend`` on small pages; the new
    rows are zeros of the shape ``to_row`` is handed."""
    pages = [jnp.zeros((2, 1 + 3 * 4, PAGE, r), jnp.float32) for r in rows]
    kw = {} if core is None else {"core": core, "to_row": lambda new: new}
    attend = _paged_attend(pages, tables, pos, PAGE, window, **kw)
    H = 4
    if core is None:
        q = jnp.zeros(q_shape or (pos.shape[0], H) + pos.shape[1:] + (64,), jnp.float32)
        new = [jnp.zeros((q.shape[0], H) + q.shape[2:], jnp.float32)] * 2
    else:
        q, new = jnp.zeros(q_shape, jnp.float32), [jnp.zeros(pos.shape + rows, jnp.float32)]
    return attend(1, q, *new)


STEP = dict(pos=jnp.asarray([3, 20, 0]), tables=jnp.zeros((3, 4), jnp.int32))
KEEPS_THE_GATHER = {
    "chunk": dict(pos=jnp.arange(8), tables=jnp.zeros((4,), jnp.int32),
                  q_shape=(1, 4, 8, 64)),
    "verify_block": dict(pos=jnp.asarray([[3, 4, 5], [20, 21, 22], [0, 1, 2]]),
                         tables=jnp.zeros((3, 4), jnp.int32)),
    "latent_core": dict(STEP, rows=(128,), q_shape=(3, 4, 128),
                        core=lambda q, gather, live: jnp.zeros(q.shape) + gather(0).sum()),
    "sliding_window": dict(STEP, window=8),
    "pages_not_in_whole_tiles": dict(STEP, rows=(4 * 24,) * 2, q_shape=(3, 4, 24)),
    "cpu_backend": dict(STEP, backend="cpu"),
    # as the engine traces a replica group's programs (serving/decode.py)
    "mesh_of_two_devices": dict(STEP, devices=2),
}


@pytest.mark.parametrize("case", KEEPS_THE_GATHER.values(), ids=KEEPS_THE_GATHER.keys())
def test_paged_attend_keeps_the_gather_except_for_a_step_on_a_tpu(case, monkeypatch):
    spy = _Spy()
    monkeypatch.setattr(paged_attention, "paged_attend_step", spy)
    case = dict(case)
    backend = case.pop("backend", "tpu")
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    mesh = tp_submesh(jax.devices()[:case.pop("devices", 1)])
    with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
        out = jax.eval_shape(lambda: _attend_once(**case))
    assert spy.calls == 0 and out.shape[0] in (1, 3)
    # the same seam does take the kernel for one query a slot on a TPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    _attend_once(**STEP)
    assert spy.calls == 1


def test_the_latent_family_hands_paged_attend_a_core_of_its_own():
    """What keeps ``latent_moe_lm`` on the gather is that its core is not
    ``_kv_core``: its serving programs say so by bringing no ``kv_heads``."""
    assert latent_moe_lm.serving_programs().kv_heads is None
    assert transformer_lm.serving_programs().kv_heads is not None
