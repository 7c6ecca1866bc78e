"""The decode step's attention kernel, ``paged_attend_step``, in interpret
mode on the CPU against ``_kv_core`` over the gathered context; the latent
family's ``latent_attend_step`` and ``latent_attend_chunk`` against
``_core_absorbed`` over the gathered table; and the rule by which
``_paged_attend`` takes a kernel or keeps the gather."""

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
    # sarvam_105b's row: ONE array of bfloat16 pages of 16 x 640 (a latent of
    # 512, a rotary key of 64, zeros), 64 heads that read the same row, the
    # value the row's latent: the step over the ragged slots, and a chunk of
    # 32 queries (two tiles of sixteen, the causal mask inside each; float32,
    # 24 queries in three tiles of eight) from a page's first row, from
    # inside a page and past several pages
    "latent_step_bf16_x64": dict(latent="step", dtype=jnp.bfloat16, tol=2e-2),
    "latent_step_f32_pages_out_of_order": dict(latent="step", dtype=jnp.float32, tol=2e-5,
                                               shuffled=True, plane=2),
    "latent_chunk_at_0": dict(latent="chunk", pos0=0, dtype=jnp.bfloat16, tol=2e-2),
    "latent_chunk_mid_page": dict(latent="chunk", pos0=PAGE + 5, dtype=jnp.bfloat16, tol=2e-2),
    "latent_chunk_past_pages": dict(latent="chunk", pos0=9 * PAGE + 11, dtype=jnp.bfloat16,
                                    tol=2e-2, shuffled=True),
    "latent_chunk_f32": dict(latent="chunk", pos0=3 * PAGE - 2, dtype=jnp.float32, tol=2e-5),
    # 24 bfloat16 queries have no tile of whole sublane tiles: one query a group
    "latent_chunk_bf16_unaligned": dict(latent="chunk", pos0=2 * PAGE + 7, dtype=jnp.bfloat16,
                                        tol=2e-2, C=24),
}

# the published widths of sarvam-105b's attention, a row of 576 in 640 lanes
LATENT_CFG = dict(latent_moe_lm.BASE_CFG, num_heads=64, qk_nope_dim=128, qk_rope_dim=64,
                  v_head_dim=128, kv_lora_rank=512)


def _latent_pair(case):
    """``(kernel form, gathered core)`` of one latent case: the family's own
    kernel form (``_absorbed_in_kernel``: the queries made rows, the kernel,
    ``W_vb``) against ``_core_absorbed`` over every table position."""
    program = case["latent"]
    cdt = jnp.dtype(case["dtype"])
    cfg = dict(LATENT_CFG, compute_dtype=cdt.name)
    H, nope, rope, v, rank = (cfg[k] for k in ("num_heads", "qk_nope_dim", "qk_rope_dim",
                                               "v_head_dim", "kv_lora_rank"))
    width = latent_moe_lm.row_width(cfg)
    assert width == 640
    if program == "step":
        pos = np.asarray(RAGGED, np.int32)
        tables = _tables(pos, P_WIDE, np.random.RandomState(1) if case.get("shuffled") else None)
        q_shape, live_at = (len(pos), H, 1, nope + rope), jnp.asarray(pos)[:, None]
    else:
        C = case.get("C", 32 if cdt == jnp.bfloat16 else 24)
        pos = case["pos0"] + np.arange(C, dtype=np.int32)
        tables = _tables(pos[-1:], P_WIDE,
                         np.random.RandomState(1) if case.get("shuffled") else None)
        q_shape, live_at = (1, H, C, nope + rope), jnp.asarray(pos)[None]
    keys = jax.random.split(jax.random.PRNGKey(3), 4)
    pages = jax.random.normal(keys[0], (3, 1 + tables.shape[0] * P_WIDE, PAGE, width),
                              jnp.float32).at[..., rank + rope:].set(0).astype(cdt)
    q = jax.random.normal(keys[1], q_shape, jnp.float32) * 0.5
    w_kb = jax.random.normal(keys[2], (rank, H, nope), jnp.float32) * 0.1
    w_vb = jax.random.normal(keys[3], (rank, H, v), jnp.float32) * 0.02
    plane = case.get("plane", 0)
    kernel = latent_moe_lm._absorbed_in_kernel(cfg, program)
    got = kernel((q, w_kb, w_vb), [pages], plane,
                 tables if program == "step" else tables[0], jnp.asarray(pos))
    rows = jnp.take(pages[plane], tables, axis=0).reshape(tables.shape[0], -1, width)
    live = (jnp.arange(P_WIDE * PAGE)[None, None] <= live_at[..., None])[:, None]
    want = latent_moe_lm._core_absorbed(q, rows, live, w_kb, w_vb,
                                        scale=latent_moe_lm.softmax_scale(cfg), cdt=cdt)
    return got, want


@pytest.mark.parametrize("case", CASES.values(), ids=CASES.keys())
def test_kernel_matches_the_gathered_attention(case):
    if "latent" in case:
        got, want = _latent_pair(case)
        assert got.shape == want.shape and got.dtype == want.dtype == jnp.float32
        # bfloat16: the weights are rounded before the online sum is divided
        # out, not after: a hundredth of the outputs' 0.3-0.5
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=case["tol"], rtol=0)
        return
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


def _one_array_step(q, k_pages, v_pages, plane, tables, pos):
    """``latent_attend_step`` on the K array alone, its row key and value."""
    S, H, dh = q.shape
    q_rows = jnp.tile(q, (1, 1, k_pages.shape[-1] // dh))
    return paged_attention.latent_attend_step(q_rows, k_pages, plane, tables, pos, scale=0.1)


def _one_array_chunk(q, k_pages, v_pages, plane, tables, pos):
    """``latent_attend_chunk``: slot 0's table, two queries ending at its position."""
    q_rows = jnp.tile(jnp.swapaxes(q, 0, 1), (1, 1, k_pages.shape[-1] // q.shape[-1]))
    return paged_attention.latent_attend_chunk(q_rows, k_pages, plane, tables[0], pos[0] - 1,
                                               scale=0.1)


@pytest.mark.parametrize("attend", [paged_attend_step, _one_array_step, _one_array_chunk],
                         ids=["k_and_v", "latent_step", "latent_chunk"])
def test_a_row_past_the_position_is_not_attended(attend):
    """Rows of a live page past ``pos`` and pages past it hold anything:
    the output must not move with them."""
    pos = np.asarray([PAGE + 3, 5], np.int32)
    k_pages, v_pages = _pages(1, 1 + 2 * P_WIDE, 4 * 64, jnp.float32)
    tables = _tables(pos, P_WIDE)
    tables = tables.at[:, 2:].set(tables[0, 0])  # dead entries point at a live page
    q = jax.random.normal(jax.random.PRNGKey(7), (2, 4, 64), jnp.float32)
    got = attend(q, k_pages, v_pages, 0, tables, jnp.asarray(pos))
    rows = jnp.arange(PAGE)[None, :, None]
    dead = (rows > 3) & (jnp.arange(k_pages.shape[1])[:, None, None] == tables[0, 1])
    moved = [jnp.where(dead, 1e4, pg[0])[None] for pg in (k_pages, v_pages)]
    again = attend(q, *moved, 0, tables, jnp.asarray(pos))
    assert np.isfinite(np.asarray(got)).all() and np.abs(np.asarray(got)).max() > 0.1
    np.testing.assert_array_equal(np.asarray(got), np.asarray(again))


@pytest.mark.parametrize("body, attend", [
    ("_attend_step", paged_attend_step),
    ("_latent_attend", lambda q, k, v, *a: paged_attention.latent_attend_step(
        jnp.tile(q, (1, 1, 4)), k, *a, scale=0.1)),
], ids=["k_and_v", "latent"])
def test_the_layers_of_an_unrolled_step_trace_the_kernel_once(body, attend):
    """The plane is an argument, not a constant of the traced body: twelve
    layers calling with twelve Python ints meet one trace and one lowering
    (``lm_big``'s engine spent 10 s of set-up on the other eleven)."""
    k_pages, v_pages = _pages(3, 1 + 2 * P_WIDE, 4 * 64, jnp.float32)
    pos = jnp.asarray([PAGE + 3, 5])
    tables = _tables(np.asarray(pos), P_WIDE)
    q = jax.random.normal(jax.random.PRNGKey(7), (2, 4, 64), jnp.float32)
    text = jax.jit(lambda k, v: sum(
        attend(q, k, v, plane, tables, pos) for plane in range(3))
    ).lower(k_pages, v_pages).as_text()
    assert text.count(f"call @{body}") == 3 and text.count(f"func.func private @{body}") == 1


class _Spy:
    def __init__(self):
        self.calls = 0

    def __call__(self, q, *pages_plane_tables_pos, **kw):
        self.calls += 1
        return jnp.zeros(q.shape, jnp.float32)


def _attend_once(pos, tables, core=None, rows=(4 * 64,) * 2, q_shape=None, window=None,
                 kernels=None):
    """One call of ``_paged_attend``'s ``attend`` on small pages; the new
    rows are zeros of the shape ``to_row`` is handed."""
    pages = [jnp.zeros((2, 1 + 3 * 4, PAGE, r), jnp.float32) for r in rows]
    kw = {} if core is None else {"core": core, "to_row": lambda new: new, "kernels": kernels}
    attend = _paged_attend(pages, tables, pos, PAGE, window, **kw)
    H = 4
    if core is None:
        q = jnp.zeros(q_shape or (pos.shape[0], H) + pos.shape[1:] + (64,), jnp.float32)
        new = [jnp.zeros((q.shape[0], H) + q.shape[2:], jnp.float32)] * 2
    else:
        q, new = jnp.zeros(q_shape, jnp.float32), [jnp.zeros(pos.shape + rows, jnp.float32)]
    return attend(1, q, *new)


def _latent_attend_once(pos, tables, rows=(128,), **kw):
    """The same through ``latent_moe_lm._attend_pages``: the family's own
    core and the kernel forms it hands ``_paged_attend`` beside it."""
    cfg = dict(latent_moe_lm.BASE_CFG, num_heads=4, qk_nope_dim=16, qk_rope_dim=8, v_head_dim=16,
               kv_lora_rank=32, compute_dtype="float32")
    pages = [jnp.zeros((2, 1 + 3 * 4, PAGE) + rows, jnp.float32)]
    attend = latent_moe_lm._attend_pages(cfg, kw.get("form", "absorbed"), pages, tables, pos, PAGE)
    N, T = (1, pos.shape[0]) if tables.ndim == 1 else (pos.shape[0], 1)
    w = jnp.zeros((32, 4, 16), jnp.float32)
    return attend(1, jnp.zeros((N, 4, T, 24), jnp.float32), jnp.zeros((N, T, 40), jnp.float32),
                  w, w)


STEP = dict(pos=jnp.asarray([3, 20, 0]), tables=jnp.zeros((3, 4), jnp.int32))
CHUNK = dict(pos=jnp.arange(8), tables=jnp.zeros((4,), jnp.int32))
KEEPS_THE_GATHER = {
    "chunk": dict(CHUNK, q_shape=(1, 4, 8, 64)),
    "verify_block": dict(pos=jnp.asarray([[3, 4, 5], [20, 21, 22], [0, 1, 2]]),
                         tables=jnp.zeros((3, 4), jnp.int32)),
    # a core of a caller's own that brings no kernel form
    "core_without_a_kernel": dict(
        STEP, rows=(128,), q_shape=(3, 4, 128),
        core=lambda q, gather, live: jnp.zeros(q.shape) + gather(0).sum()),
    "sliding_window": dict(STEP, window=8),
    "pages_not_in_whole_tiles": dict(STEP, rows=(4 * 24,) * 2, q_shape=(3, 4, 24)),
    "cpu_backend": dict(STEP, backend="cpu"),
    # as the engine traces a replica group's programs (serving/decode.py)
    "mesh_of_two_devices": dict(STEP, devices=2),
    # the latent family: its two kernel forms under the same rule
    "latent_step_cpu_backend": dict(STEP, latent=True, backend="cpu"),
    "latent_chunk_mesh_of_two_devices": dict(CHUNK, latent=True, devices=2),
    "latent_row_not_in_whole_tiles": dict(STEP, latent=True, rows=(40,)),
    "latent_expanded_form": dict(CHUNK, latent=True, form="expanded"),
}


@pytest.mark.parametrize("case", KEEPS_THE_GATHER.values(), ids=KEEPS_THE_GATHER.keys())
def test_paged_attend_keeps_the_gather_except_for_a_step_on_a_tpu(case, monkeypatch):
    """... and, with the latent family's one array, for its step and its
    chunk on a TPU."""
    spies = {name: _Spy() for name in
             ("paged_attend_step", "latent_attend_step", "latent_attend_chunk")}
    for name, spy in spies.items():
        monkeypatch.setattr(paged_attention, name, spy)
    calls = lambda: {name: spy.calls for name, spy in spies.items() if spy.calls}
    case = dict(case)
    backend = case.pop("backend", "tpu")
    once = _latent_attend_once if case.pop("latent", False) else _attend_once
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    mesh = tp_submesh(jax.devices()[:case.pop("devices", 1)])
    with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
        out = jax.eval_shape(lambda: once(**case))
    assert not calls() and out.shape[0] in (1, 3)
    # the same seams do take the kernels on a TPU: one query a slot over K
    # and V pages; a step and a chunk over the latent family's one array
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    _attend_once(**STEP)
    assert calls() == {"paged_attend_step": 1}
    assert _latent_attend_once(**STEP).shape == (3, 4, 1, 16)
    assert calls() == {"paged_attend_step": 1, "latent_attend_step": 1}
    assert _latent_attend_once(**CHUNK).shape == (1, 4, 8, 16)
    assert calls() == {"paged_attend_step": 1, "latent_attend_step": 1, "latent_attend_chunk": 1}


def test_the_latent_family_hands_paged_attend_its_kernel_forms_beside_its_core(monkeypatch):
    """What takes ``latent_moe_lm`` to its kernels is what it hands
    ``_paged_attend``: a core of its own and, for the absorbed form, a
    kernel form for the step and for the chunk. Its serving programs bring
    no ``kv_heads`` (a row is not heads side by side) and say which programs
    attend in a kernel by the same rule, the row as the one head."""
    progs = latent_moe_lm.serving_programs()
    assert progs.kv_heads is None and transformer_lm.serving_programs().kv_heads is not None
    cfg = dict(latent_moe_lm.BASE_CFG)
    pages = jax.ShapeDtypeStruct((3, 9, PAGE, latent_moe_lm.row_width(cfg)), jnp.bfloat16)
    narrow = jax.ShapeDtypeStruct((3, 9, PAGE, 576), jnp.bfloat16)
    kv_cfg = dict(transformer_lm.BASE_CFG)
    kv_pages = jax.ShapeDtypeStruct(transformer_lm.paged_cache_shape(kv_cfg, 9, PAGE), jnp.float32)
    kv_progs = transformer_lm.serving_programs()
    assert progs.attends_in_kernel(cfg, pages, PAGE) == ()  # a CPU keeps the gather
    assert kv_progs.attends_in_kernel(kv_cfg, kv_pages, PAGE) == ()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert progs.attends_in_kernel(cfg, pages, PAGE) == ("step", "chunk")
    assert progs.attends_in_kernel(cfg, narrow, PAGE) == ()
    assert kv_progs.attends_in_kernel(kv_cfg, kv_pages, PAGE) == ("step",)
    assert kv_progs.attends_in_kernel(dict(kv_cfg, attention_window=8), kv_pages, PAGE) == ()
