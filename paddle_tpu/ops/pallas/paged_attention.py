"""One decode step's attention over a paged K and V cache as one Mosaic
kernel, ``paged_attend_step``.

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
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from paddle_tpu.core.enforce import enforce

__all__ = ["paged_attend_step", "step_fits"]

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
