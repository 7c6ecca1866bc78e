"""Attention over a paged cache that reads the pages a sequence holds where
they lie: one decode step over K and V pages as one Mosaic kernel,
``paged_attend_step``, and, for a cache of ONE array whose row is key and
value (latent attention, absorbed), ``latent_attend_step`` and
``latent_attend_chunk`` (the second half of this file).

The serving step (``models/transformer_lm.py::_paged_attend``) has one query
a slot and a page table row a slot. Its XLA form gathers every slot's whole
table, ``max_context`` rows whatever is live, and attends under a mask. This
kernel leaves the page arrays in HBM and, slot by slot, copies in the pages
the slot holds, ``page_tables[s, 0 : pos[s] // page_size + 1]`` of the one
plane, several a step and the next step's in flight behind the arithmetic.
A page the slot does not hold costs no copy; the last step of a slot
computes on a whole buffer, masked past ``pos[s]``.

Layout. A page is ``[page_size, H_kv * dh]``, a position's heads side by
side, contiguous in HBM, and is never sliced by head. The scores of all
heads come out of one matmul of the buffered rows ``[T, H_kv * dh]`` with a
block-diagonal arrangement of the queries ``[H, H_kv * dh]`` (head ``h``'s
query in the lanes of its key-value head ``h // (H / H_kv)``, zero
elsewhere), the weighted values out of ``p [H, T] @ v [T, H_kv * dh]``, of
which head ``h`` keeps its own lanes. The MXU multiplies ``H_kv`` times more
than it must, which costs less than cutting rows into heads would: the step
is bound by the copies. Softmax is online, in float32, across a slot's steps.

The latent form is the same schedule with one row form less: one key-value
head (no block-diagonal queries, no lanes to keep), a page copied once into
one buffer that is the key (the whole row) and the value (its first lanes),
the softmax's scale an argument. Its groups (a slot's heads; a tile of a
chunk's queries with their heads, each row masked at its own position) are
a grid axis, so a chunk's queries are never whole in VMEM. The two bodies
share helpers and nothing else: the K and V kernel is the text it was.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from paddle_tpu.core.enforce import enforce

__all__ = ["latent_attend_chunk", "latent_attend_step", "paged_attend_step", "step_fits"]

LANES = 128
# rows a step of the kernel holds of K and of V, twice each (this step's and
# the next one's): 128 rows of 2048 bfloat16 or 1024 float32 are 512 KB
STEP_ROWS = 128
MASKED = -1e9  # as ``_attend_cached`` masks


def _sublanes(dtype) -> int:
    """Rows of one tile of the chip's memory: 8 of 32 bits, 16 of 16."""
    return 32 // jnp.dtype(dtype).itemsize


def step_fits(pages_shape, dtype, page_size: int, head_dim: int) -> bool:
    """Whether the kernel can take these page arrays as they lie: a row of
    whole lane tiles, a page of whole sublane tiles, a head that divides a
    lane tile or is a multiple of one."""
    row = pages_shape[-1]
    return (row % LANES == 0 and page_size % _sublanes(dtype) == 0
            and (LANES % head_dim == 0 or head_dim % LANES == 0))


def _step_kernel(pt_ref, pos_ref, plane_ref, q_ref, own_ref, k_hbm, v_hbm, o_ref,
                 kbuf, vbuf, sem, m_ref, l_ref, acc_ref, *, page_size: int,
                 pages_a_step: int, scale: float):
    """Every slot in turn, a slot's live pages ``pages_a_step`` at a time.
    ``pt_ref`` [S, P], ``pos_ref`` [S] and ``plane_ref`` [1] live in SMEM;
    ``q_ref`` [S, H, row] holds the block-diagonal queries and ``own_ref``
    [H, row] is 1 on the lanes of each head's own key-value head; ``k_hbm``
    and ``v_hbm`` are the page arrays, whole, in HBM. ``kbuf`` and ``vbuf``
    [2, T, row] are filled by one copy a page; the steps of all slots form
    one sequence and step ``g`` computes on buffer ``g % 2`` while the copies
    of step ``g + 1`` (the slot's next, or the next slot's first) run."""
    from jax.experimental.pallas import tpu as pltpu

    S, H, row = q_ref.shape
    W = o_ref.shape[-1]
    C, T = pages_a_step, pages_a_step * page_size
    plane = plane_ref[0]
    # float32 pages are multiplied as float32 (the gather's product on the
    # vector unit is exact); bfloat16 operands are exact in one pass
    exact = jax.lax.Precision.HIGHEST if kbuf.dtype == jnp.float32 else None

    def each_copy(s, c, b, do):
        n_pages = pos_ref[s] // page_size + 1
        for i in range(C):
            @pl.when(c * C + i < n_pages)
            def _():
                page = pt_ref[s, c * C + i]
                for j, (hbm, buf) in enumerate(((k_hbm, kbuf), (v_hbm, vbuf))):
                    do(pltpu.make_async_copy(
                        hbm.at[plane, page], buf.at[b, pl.ds(i * page_size, page_size)],
                        sem.at[j, b]))

    start = functools.partial(each_copy, do=lambda copy: copy.start())
    # a DMA's wait, not a thread's
    wait = functools.partial(each_copy, do=lambda copy: copy.wait())  # lint: allow

    # a weight of exactly 0 times a row no copy has written must be 0
    vbuf[...] = jnp.zeros_like(vbuf)
    start(0, 0, 0)

    def one_slot(s, g):
        pos = pos_ref[s]
        steps = pos // T + 1
        m_ref[...] = jnp.full_like(m_ref, -1e30)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

        def one_step(c, g):
            b = g % 2
            last = c + 1 == steps
            s_next, c_next = jnp.where(last, s + 1, s), jnp.where(last, 0, c + 1)

            @pl.when(s_next < S)
            def _():
                start(s_next, c_next, 1 - b)

            wait(s, c, b)
            k, v = kbuf[b], vbuf[b]
            scores = jax.lax.dot_general(
                q_ref[s], k, (((1,), (1,)), ((), ())), precision=exact,
                preferred_element_type=jnp.float32) * scale  # [H, T]
            at = c * T + jax.lax.broadcasted_iota(jnp.int32, (H, T), 1)
            scores = jnp.where(at <= pos, scores, MASKED)
            m_prev = m_ref[...]
            m_new = jnp.maximum(m_prev, jnp.max(scores, axis=1, keepdims=True))
            p = jnp.exp(scores - m_new)
            alpha = jnp.exp(m_prev - m_new)
            m_ref[...] = m_new
            l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
            acc_ref[...] = alpha * acc_ref[...] + jnp.dot(
                p.astype(v.dtype), v, precision=exact, preferred_element_type=jnp.float32)
            return g + 1

        g = jax.lax.fori_loop(0, steps, one_step, g)
        # a head keeps the lanes of its own key-value head
        out = acc_ref[:, 0:W] * own_ref[:, 0:W]
        for j in range(1, row // W):
            out += acc_ref[:, j * W:(j + 1) * W] * own_ref[:, j * W:(j + 1) * W]
        o_ref[s] = out / l_ref[...]
        return g

    jax.lax.fori_loop(0, S, one_slot, 0)


def paged_attend_step(q, k_pages, v_pages, plane, page_tables, pos, *,
                      interpret: Optional[bool] = None):
    """Attention of one query a slot over the slot's live pages of plane
    ``plane``, the row at ``pos[s]`` included.

    ``q`` [S, H, dh] (rotated, as the cached keys are); ``k_pages`` and
    ``v_pages`` [planes, num_pages, page_size, H_kv * dh], read where they
    lie and not written; ``plane`` an int32 scalar, traced or not;
    ``page_tables`` [S, P] int32; ``pos`` [S] int32, slot ``s`` attending
    positions ``0 .. pos[s]``. The queries are multiplied in the pages'
    dtype with float32 accumulation, the softmax is float32, its weights
    are multiplied in the pages' dtype with float32 accumulation. Returns
    the context [S, H, dh] in the wider of ``q``'s and the pages' dtype.

    The plane is an argument of one jitted body, so the layers of an
    unrolled step trace and lower the kernel once between them (twelve
    times over cost ``lm_big``'s engine 10 s of set-up)."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _attend_step(q, k_pages, v_pages, jnp.asarray(plane, jnp.int32), page_tables, pos,
                        interpret=interpret,
                        pages_a_step=max(1, STEP_ROWS // k_pages.shape[2]))


@functools.partial(jax.jit, static_argnames=("interpret", "pages_a_step"))
def _attend_step(q, k_pages, v_pages, plane, page_tables, pos, *, interpret, pages_a_step):
    from jax.experimental.pallas import tpu as pltpu

    S, H, dh = q.shape
    _, _, page_size, row = k_pages.shape
    H_kv = row // dh
    enforce(k_pages.shape == v_pages.shape and k_pages.dtype == v_pages.dtype
            and row == H_kv * dh and H % H_kv == 0,
            f"paged_attend_step: queries {q.shape} do not match pages "
            f"{k_pages.shape} {k_pages.dtype} / {v_pages.shape} {v_pages.dtype}")
    enforce(page_tables.shape[0] == S and pos.shape == (S,),
            f"paged_attend_step: {page_tables.shape} tables and {pos.shape} "
            f"positions for {S} slots")
    enforce(interpret or step_fits(k_pages.shape, k_pages.dtype, page_size, dh),
            f"paged_attend_step: pages {k_pages.shape} {k_pages.dtype} do not "
            "lie in whole tiles")
    T = pages_a_step * page_size
    # the width heads' own lanes are folded to: a head, a lane tile of heads, or the row
    W = dh if dh % LANES == 0 else LANES if row % LANES == 0 and LANES % dh == 0 else row
    # head h's query in the lanes of key-value head h // (H / H_kv)
    own = np.arange(H)[:, None] // (H // H_kv) == np.arange(H_kv)[None, :]  # [H, H_kv]
    q_rows = jnp.where(own[None, :, :, None], q[:, :, None, :], 0).reshape(S, H, row)
    own_rows = jnp.asarray(np.repeat(own, dh, axis=1), jnp.float32)
    whole = lambda shape: pl.BlockSpec(shape, lambda i, *_: (0,) * len(shape))
    out = pl.pallas_call(
        functools.partial(_step_kernel, page_size=page_size, pages_a_step=pages_a_step,
                          scale=1.0 / np.sqrt(dh)),
        name="paged_attend_step",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(1,),
            in_specs=[whole((S, H, row)), whole((H, row)),
                      pl.BlockSpec(memory_space=pl.ANY), pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=whole((S, H, W)),
            scratch_shapes=[
                pltpu.VMEM((2, T, row), k_pages.dtype),
                pltpu.VMEM((2, T, row), v_pages.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM((H, 1), jnp.float32),
                pltpu.VMEM((H, 1), jnp.float32),
                pltpu.VMEM((H, row), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((S, H, W), jnp.float32),
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(page_tables.astype(jnp.int32), pos.astype(jnp.int32),
      plane.reshape(1), q_rows.astype(k_pages.dtype), own_rows,
      k_pages, v_pages)
    # where a lane tile holds several heads, a head's own is the one not zeroed
    ctx = out.reshape(S, H, W // dh, dh).sum(2)
    return ctx.astype(jnp.promote_types(q.dtype, v_pages.dtype))


# ---- one page array whose row is key and value: the latent form -----------

# rows of the one array a step of the latent kernels holds, twice. A row is
# 640 lanes where a K and a V row of the cells above are 2048 together, and a
# page a copy of 20 KB: the decode step's kernel, bound by its copies and its
# 64-row products, read a plane's live rows of sarvam_105b's cell in 1.158 ms
# at 256 rows, 0.947 at 512, 0.866 at 1024 (332 GB/s), 0.833 at 2048; the
# chunk's, bound by the matrix unit, took 512 (6.14 ms at position 11776
# against 6.73 at 256; 1024 does not fit beside a tile of 16 queries).
# PERF.md, PR 44
LATENT_STEP_ROWS = 1024
LATENT_CHUNK_ROWS = 512
# queries of a chunk attended as one tile: 16 queries of 64 heads are 1024
# rows of the matrix unit against the buffered rows (69-75 % of its peak; 8
# queries 57-62 %, and every tile reads the sequence's live pages again)
CHUNK_TILE_QUERIES = 16


def _latent_kernel(pt_ref, last_ref, plane_ref, q_ref, pages_hbm, o_ref,
                   buf, sem, count_ref, m_ref, l_ref, acc_ref, *, page_size: int,
                   pages_a_step: int, scale: float, one_table: bool):
    """Group ``g`` of the grid: ``q_ref`` [H, row], a slot's one query, or
    [H, Q, row], a tile of a chunk's queries under each head (row ``h * Q +
    i`` of the products is query ``i``), the last query at position
    ``last_ref[g]`` and each before it one position earlier, over the pages
    ``0 .. last_ref[g] // page_size`` of its table (``pt_ref[g]``, or
    ``pt_ref[0]`` where all groups are one sequence), ``pages_a_step`` at a
    time: ``_step_kernel``'s schedule over a grid. A row attends the
    positions up to its query's. ``pages_hbm`` is the page array, whole, in
    HBM; a page is copied once into ``buf`` [2, T, row] and is the key (the
    whole row) and the value (its first ``o_ref.shape[-1]`` lanes). The
    steps of all groups form one sequence, counted in ``count_ref`` across
    the grid: step ``n`` computes on buffer ``n % 2`` while the copies of
    step ``n + 1`` (the group's next, or the next group's first) run."""
    from jax.experimental.pallas import tpu as pltpu

    g, G = pl.program_id(0), pl.num_programs(0)
    W = o_ref.shape[-1]
    C, T = pages_a_step, pages_a_step * page_size
    plane = plane_ref[0]
    cdt = q_ref.dtype
    exact = jax.lax.Precision.HIGHEST if cdt == jnp.float32 else None

    def swept(grp):  # a table entry past the table is read by nobody: the gather clips too
        return jnp.minimum(last_ref[grp], pt_ref.shape[1] * page_size - 1)

    def each_copy(grp, c, b, do):
        table = 0 if one_table else grp
        held = jnp.minimum(swept(grp) // page_size + 1 - c * C, C)

        def one_page(i, _):
            do(pltpu.make_async_copy(
                pages_hbm.at[plane, pt_ref[table, c * C + i]],
                buf.at[b, pl.ds(pl.multiple_of(i * page_size, page_size), page_size)],
                sem.at[b]))
            return 0

        jax.lax.fori_loop(0, held, one_page, 0)

    start = functools.partial(each_copy, do=lambda copy: copy.start())
    # a DMA's wait, not a thread's
    wait = functools.partial(each_copy, do=lambda copy: copy.wait())  # lint: allow

    @pl.when(g == 0)
    def _():
        # a weight of exactly 0 times a row no copy has written must be 0
        buf[...] = jnp.zeros_like(buf)
        count_ref[0] = 0
        start(0, 0, 0)

    steps = swept(g) // T + 1
    R, Q = acc_ref.shape[0], 1 if len(q_ref.shape) == 2 else q_ref.shape[1]
    # the position of a row's query: Q queries end at last_ref[g]
    lim = last_ref[g] - (Q - 1) + jax.lax.broadcasted_iota(jnp.int32, (R, 1), 0) % Q
    m_ref[...] = jnp.full_like(m_ref, -1e30)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    def one_step(c, n):
        b = n % 2
        last = c + 1 == steps
        g_next, c_next = jnp.where(last, g + 1, g), jnp.where(last, 0, c + 1)

        @pl.when(g_next < G)
        def _():
            start(g_next, c_next, 1 - b)

        wait(g, c, b)
        scores = jax.lax.dot_general(
            q_ref[...].reshape(R, -1), buf[b].astype(cdt), (((1,), (1,)), ((), ())),
            precision=exact,
            preferred_element_type=jnp.float32) * scale  # [R, T]
        at = c * T + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
        scores = jnp.where(at <= lim, scores, MASKED)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(scores, axis=1, keepdims=True))
        p = jnp.exp(scores - m_new)
        alpha = jnp.exp(m_prev - m_new)
        m_ref[...] = m_new
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = alpha * acc_ref[...] + jnp.dot(
            p.astype(cdt), buf[b, :, 0:W].astype(cdt), precision=exact,
            preferred_element_type=jnp.float32)
        return n + 1

    count_ref[0] = jax.lax.fori_loop(0, steps, one_step, count_ref[0])
    o_ref[...] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype).reshape(o_ref.shape)


@functools.partial(jax.jit, static_argnames=(
    "name", "scale", "queries", "value_width", "pages_a_step", "interpret"))
def _latent_attend(q, pages, plane, page_tables, last, *, name, scale, queries, value_width,
                   pages_a_step, interpret):
    """``q`` in the dtype the products are made in: [G, H, row], a group a
    slot (``queries`` None), or [H, C, row], a group ``queries`` of the one
    sequence's ``C``; ``page_tables`` [G, P] or [1, P]; ``last`` [G] the
    position of a group's last query -> ``q``'s shape with ``value_width``
    for the row, in ``q``'s dtype."""
    from jax.experimental.pallas import tpu as pltpu

    H, row = q.shape[-2 if queries is None else 0], q.shape[-1]
    (G,) = last.shape
    page_size = pages.shape[2]
    W = row if value_width is None else value_width
    enforce(pages.shape[-1] == row and (W == row or (W < row and W % LANES == 0)),
            f"{name}: queries {q.shape} and values of {W} do not match pages {pages.shape}")
    enforce(page_tables.shape[0] in (1, G)
            and (q.shape[0] == G if queries is None else q.shape[1] == G * queries),
            f"{name}: {page_tables.shape} tables and {last.shape} positions for queries "
            f"{q.shape}, {queries} a group")
    enforce(interpret or step_fits(pages.shape, pages.dtype, page_size, row),
            f"{name}: pages {pages.shape} {pages.dtype} do not lie in whole tiles")
    T = pages_a_step * page_size
    if queries is None:  # a slot's heads
        a_group = lambda width: pl.BlockSpec((None, H, width), lambda g, *_: (g, 0, 0))
    else:  # a tile of the chunk's queries under every head
        a_group = lambda width: pl.BlockSpec((H, queries, width), lambda g, *_: (0, g, 0))
    R = H * (queries or 1)
    return pl.pallas_call(
        functools.partial(_latent_kernel, page_size=page_size, pages_a_step=pages_a_step,
                          scale=scale, one_table=page_tables.shape[0] == 1),
        name=name,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(G,),
            in_specs=[a_group(row), pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=a_group(W),
            scratch_shapes=[
                pltpu.VMEM((2, T, row), pages.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SMEM((1,), jnp.int32),
                pltpu.VMEM((R, 1), jnp.float32),
                pltpu.VMEM((R, 1), jnp.float32),
                pltpu.VMEM((R, W), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct(q.shape[:-1] + (W,), q.dtype),
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(page_tables.astype(jnp.int32), last.astype(jnp.int32), plane.reshape(1), q, pages)


def _latent_call(name, *, q, pages, plane, page_tables, last, queries, rows, scale, value_width,
                 interpret):
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _latent_attend(
        q, pages, jnp.asarray(plane, jnp.int32), page_tables, last, name=name,
        scale=float(scale), queries=queries, value_width=value_width, interpret=interpret,
        pages_a_step=max(1, rows // pages.shape[2]))


def latent_attend_step(q, pages, plane, page_tables, pos, *, scale: float,
                       value_width: Optional[int] = None, interpret: Optional[bool] = None):
    """One query a slot over the slot's live pages of plane ``plane`` of the
    ONE page array ``pages`` [planes, num_pages, page_size, row], whose row
    is the key and, in its first ``value_width`` lanes (a multiple of 128, or
    the row), the value: latent attention in its absorbed form, one
    key-value head that all ``H`` query heads read. ``q`` [S, H, row], the
    queries as rows of the cache (zeros where the row holds none), in the
    dtype the products are made in (float32 accumulation, float32 softmax,
    its weights rounded to that dtype before the second product);
    ``page_tables`` [S, P]; ``pos`` [S], slot ``s`` attending ``0 .. pos[s]``.
    Returns ``sum_t softmax(scale * q . row_t) row_t[:value_width]``,
    [S, H, value_width], summed in float32 and rounded once to ``q``'s dtype
    (what the next product takes: a float32 context of a chunk is 67 MB a
    layer written, read and converted). As :func:`paged_attend_step`, the
    plane is an argument of one jitted body."""
    return _latent_call("latent_attend_step", q=q, pages=pages, plane=plane,
                        page_tables=page_tables, last=pos, queries=None, rows=LATENT_STEP_ROWS,
                        scale=scale, value_width=value_width, interpret=interpret)


def latent_attend_chunk(q, pages, plane, page_table, pos0, *, scale: float,
                        value_width: Optional[int] = None, interpret: Optional[bool] = None):
    """A chunk's queries, ``q`` [H, C, row] (a head's queries together, as
    the product that makes them leaves them) at positions ``pos0 .. pos0 +
    C - 1`` of the one sequence whose table ``page_table`` [P] is, each over
    the rows up to its own position (the chunk's own rows are read back
    through the pages like any others): :func:`latent_attend_step`'s body
    with ``CHUNK_TILE_QUERIES`` queries under every head as the rows of one
    group (one query where ``C`` has no such divisor of whole sublane
    tiles: correct, and slow), a group sweeping the pages up to its last
    query's and no further. Returns [H, C, value_width] in ``q``'s dtype."""
    C = q.shape[1]
    tq = int(np.gcd(C, CHUNK_TILE_QUERIES))
    call = functools.partial(_latent_call, "latent_attend_chunk", pages=pages, plane=plane,
                             page_tables=page_table[None], rows=LATENT_CHUNK_ROWS, scale=scale,
                             value_width=value_width, interpret=interpret)
    if tq % _sublanes(q.dtype):
        # the kernel folds a tile of whole sublane tiles under the heads:
        # where C has none, a query is a group, as a step's slot is
        at = pos0 + jnp.arange(C, dtype=jnp.int32)
        return jnp.swapaxes(call(q=jnp.swapaxes(q, 0, 1), last=at, queries=None), 0, 1)
    return call(q=q, last=pos0 + jnp.arange(tq - 1, C, tq, dtype=jnp.int32), queries=tq)
