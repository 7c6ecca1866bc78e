"""Decoder-only LM with multi-head latent attention and a sparse expert
layer (DeepSeek-V2's block, as sarvamai/sarvam-105b configures it: no query
compression, a sigmoid router with a selection bias, one shared expert).

Block, pre-norm, no bias: ``h = x + Attn(RMSNorm(x))``, ``y = h +
FFN_i(RMSNorm(h))``; a final RMSNorm and an untied head.

``Attn(n)``: ``q = W_q n`` as ``H`` heads of ``nope + rope`` numbers, an
RMSNorm with a learned scale over each head, RoPE on the last ``rope``.
``W_kv_a n`` is ``rank + rope`` wide: ``c = RMSNorm(first rank)`` and
``k_rope = RoPE(last rope)``, one rotary key shared by all heads. **The
cached row of a token is** ``[c ; k_rope]``: one row per position and layer,
not a K and a V per head. ``W_kv_b`` [rank, H, nope + v] gives head ``h`` its
``W_kb_h`` [rank, nope] and ``W_vb_h`` [rank, v]. Two forms of the same
attention (:data:`CORES`):

* expanded: ``k_h = [W_kb_h^T c ; k_rope]``, ``v_h = W_vb_h^T c`` for every
  cached position, then plain softmax attention. Fewer operations when
  many queries share a context; training runs it.
* absorbed: ``q_lat_h = W_kb_h q_nope_h``, score ``= [q_lat_h ; q_rope_h] .
  [c_s ; k_rope_s]``, ``ctx_h = sum_s a_s c_s``, ``o_h = W_vb_h^T ctx_h``: the
  heads attend over the cached rows as they lie: one key-value head whose
  value is the key's own row. A decode step runs it, and a prefill chunk
  too (``latent_prefill_chunk``'s ``form``: faster on the chip).

Under ``rope_scaling`` (YaRN, ``ops/attention.yarn_inv_freq``) the softmax
scale is ``(nope + rope) ** -0.5 * yarn_mscale(factor, mscale_all_dim) ** 2``.

``FFN_i``: a SwiGLU of width ``d_inner`` for ``i < first_dense``; after that
``s = sigmoid(W_r n)`` in float32 over the router's full width, the
``experts_per_token`` largest ``s + b`` selected (``b`` a bias that enters
the selection only), weights ``routed_scaling * s_e / sum_selected s``, and
``FFN_i(n) = sum_{e selected} w_e E_e(n) + Shared(n)``, every expert a SwiGLU
of width ``moe_d_inner``. ``experts_held`` (first index, count) says which
experts' weights this model holds: the sum runs over the selected experts
that are held, the others' terms are left out (``ops/moe.py``: one chip's
share of an expert-parallel layer; nothing stands in for the other chips).
``None`` holds all of them, which is the whole layer. The held experts'
matrices are stacked in their order, ``[count, d, f]`` (gate, fc1) and
``[count, f, d]`` (fc2): what the grouped matmul reads. A checkpoint that
holds a matrix an expert is stacked once, at load (:func:`stack_experts`).

The block is written once, :func:`block`; training, a prefill chunk and a
decode step differ in the ``attend`` they hand it: none of a cache, a slot's
pages, every slot's pages. The paged ones are
``transformer_lm._paged_attend``, the one body that writes a row a position
into ``[L, page, offset, row]`` and attends back through a page table, with
one array, this module's cores over the gathered table and, for the absorbed
form, its kernel forms over the pages that are live
(``ops/pallas/paged_attention.py``: ``latent_attend_step``,
``latent_attend_chunk``), which a TPU's programs take.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

import paddle_tpu as pt
from paddle_tpu.core import profiler as prof
from paddle_tpu.core.enforce import enforce
from paddle_tpu.models import ModelSpec, ServingPrograms
from paddle_tpu.models.retention_lm import (
    _dict_params, _embed, _enforce_sampling, _frame_params, _logits,
    _next_token_loss, _ops,
)
from paddle_tpu.models.transformer_lm import (
    _live_mask, _paged_attend, sample_logits, step_attends_in_kernel,
)
from paddle_tpu.ops import moe
from paddle_tpu.ops.attention import apply_rope, rope_tables, yarn_mscale

__all__ = [
    "BASE_CFG", "CORES", "block", "get_model", "head_block_for", "held_experts",
    "latent_cache_specs", "latent_decode_step", "latent_prefill_chunk", "lm_forward",
    "param_shapes", "row_width", "serving_programs", "softmax_scale", "span_attrs",
    "stack_experts",
]

BASE_CFG = dict(
    family="latent_moe_lm",
    vocab=32000,
    d_model=512,
    num_heads=8,
    qk_nope_dim=64,
    qk_rope_dim=32,
    v_head_dim=64,
    kv_lora_rank=128,
    d_inner=1536,          # the leading dense layers' SwiGLU
    moe_d_inner=256,       # every routed and shared expert's
    n_layers=3,
    first_dense=1,         # layers before the first expert layer
    num_experts=16,        # the router's width
    experts_per_token=4,
    experts_held=None,     # (first, count) of the experts held here; None = all
    routed_scaling=2.5,
    max_len=2048,
    rope_theta=1e4,
    rope_scaling=None,     # a published ``rope_scaling`` group (YaRN)
    rms_eps=1e-6,
    param_dtype="bfloat16",
    compute_dtype="bfloat16",
)


LANES = 128


def row_width(cfg: dict) -> int:
    """Numbers a cached row holds: the latent, the shared rotary key, and
    zeros up to a multiple of 128. The chip keeps a trailing axis in tiles of
    128 lanes, so a row of 576 takes 640 either way; spelled 576 wide, the
    chip's compact layout puts the page axis minor-most instead and every
    program converts the array whole on entry, on exit and at each layer
    (15 GB of copies a step at the published sizes, compile for a described
    v5e). A multiple of 128 is held as spelled."""
    return -(-(cfg["kv_lora_rank"] + cfg["qk_rope_dim"]) // LANES) * LANES


def held_experts(cfg: dict):
    return tuple(cfg["experts_held"]) if cfg["experts_held"] else (0, cfg["num_experts"])


def softmax_scale(cfg: dict) -> float:
    scale = (cfg["qk_nope_dim"] + cfg["qk_rope_dim"]) ** -0.5
    rs = cfg["rope_scaling"]
    if rs:
        scale *= yarn_mscale(rs["factor"], rs.get("mscale_all_dim", 0.0)) ** 2
    return scale


# -- the attention core, two forms -----------------------------------------

def _softmax_rows(s, live):
    return jax.nn.softmax(jnp.where(live, s, -1e9), axis=-1)


_mm = functools.partial(jnp.einsum, preferred_element_type=jnp.float32)


def _queries_as_rows(q, w_kb, width: int, cdt):
    """The absorbed queries as rows of the cache: ``q`` [B, H, Q, nope +
    rope] float32 -> ``[W_kb q_nope ; q_rope ; zeros]``, [B, H, Q, width] in
    ``cdt``. ``W_kb`` is filled with zero rows to the width, so the product
    comes out as the row it will be (float32 sums rounded once) and the
    rotary part is written into its lanes: a concatenation and a pad were
    two more passes over a chunk's 42 MB of queries."""
    rank, _, nope = w_kb.shape
    w = jnp.pad(w_kb.astype(cdt), ((0, width - rank), (0, 0), (0, 0)))
    q_row = _mm("bhqd,chd->bhqc", q[..., :nope].astype(cdt), w).astype(cdt)
    return q_row.at[..., rank:rank + q.shape[-1] - nope].set(q[..., nope:].astype(cdt))


def _core_absorbed(q, rows, live, w_kb, w_vb, *, scale, cdt):
    """``q`` [B, H, Q, nope + rope] float32 over ``rows`` [B, T, row] as they
    lie in the cache (latent, rotary key, zeros); ``live`` [B, 1, Q, T];
    ``w_kb`` [rank, H, nope], ``w_vb`` [rank, H, v]. Returns [B, H, Q, v]
    float32. Both sides contract the whole row (the query's is filled with
    zeros, the value's columns past the latent are dropped after): slicing
    the gathered context would copy it."""
    rank = w_kb.shape[0]
    rows = rows.astype(cdt)
    q_row = _queries_as_rows(q, w_kb, rows.shape[-1], cdt)
    a = _softmax_rows(_mm("bhqr,btr->bhqt", q_row, rows) * scale, live)
    ctx = _mm("bhqt,btr->bhqr", a.astype(cdt), rows)[..., :rank]
    return _mm("bhqc,chd->bhqd", ctx.astype(cdt), w_vb.astype(cdt))


# the serving programs whose absorbed attention has a kernel form
KERNEL_PROGRAMS = ("step", "chunk")


def _absorbed_in_kernel(cfg, program: str):
    """The absorbed core's kernel form for ``program``, as ``_paged_attend``
    takes it: the same products in the same dtypes over the pages a
    sequence holds, copied where they lie, the softmax online
    (``ops/pallas/paged_attention.py``); ``W_kb`` before and ``W_vb`` after
    stay einsums. Each layer's call counts ``mla.kernel.<program>`` as it
    is traced."""
    from paddle_tpu.ops.pallas import paged_attention as pa

    scale, cdt, rank = softmax_scale(cfg), jnp.dtype(cfg["compute_dtype"]), cfg["kv_lora_rank"]
    # the value is the row's latent: whole lane tiles of it, or the row
    value_width = rank if rank % LANES == 0 else None

    def over_live_pages(asked, pages, plane, page_tables, pos):
        q, w_kb, w_vb = asked  # q [S, H, 1, .] of a step, [1, H, C, .] of a chunk
        prof.inc_counter(f"mla.kernel.{program}")
        q_row = _queries_as_rows(q, w_kb, pages[0].shape[-1], cdt)
        with jax.named_scope("latent_attend"):
            if program == "step":
                ctx = pa.latent_attend_step(q_row[:, :, 0], pages[0], plane, page_tables, pos,
                                            scale=scale, value_width=value_width)[:, :, None]
            else:
                ctx = pa.latent_attend_chunk(q_row[0], pages[0], plane, page_tables, pos[0],
                                             scale=scale, value_width=value_width)[None]
        return _mm("bhqc,chd->bhqd", ctx[..., :rank], w_vb.astype(cdt))  # ctx in cdt

    return over_live_pages


def _core_expanded(q, rows, live, w_kb, w_vb, *, scale, cdt):
    """The same attention with every cached position's per-head key and
    value made from its latent first."""
    nope, rank = w_kb.shape[-1], w_kb.shape[0]
    rope = q.shape[-1] - nope
    c, k_rope = rows[..., :rank].astype(cdt), rows[..., rank:rank + rope].astype(cdt)
    k_nope = _mm("btc,chd->bhtd", c, w_kb.astype(cdt)).astype(cdt)
    v = _mm("btc,chd->bhtd", c, w_vb.astype(cdt)).astype(cdt)
    s = (_mm("bhqd,bhtd->bhqt", q[..., :nope].astype(cdt), k_nope)
         + _mm("bhqd,btd->bhqt", q[..., nope:].astype(cdt), k_rope))
    return _mm("bhqt,bhtd->bhqd", _softmax_rows(s * scale, live).astype(cdt), v)


CORES = {"absorbed": _core_absorbed, "expanded": _core_expanded}

_SCORE_BYTES = 512 * 1024 * 1024


def head_block_for(batch: int, heads: int, queries: int, context: int) -> int:
    """Heads scored at once by the gathering cores: all of them while their
    float32 scores ``[batch, heads, queries, context]`` stay under 512 MiB,
    else ``heads`` halved until they do. On the chip a chunk of 512 queries
    over 16384 gathered rows took 86.5 ms with 64 heads at once (2 GiB of
    scores), 74.5 with 16 (512 MiB) and 74.7 with 8 (PERF.md, PR 31): a
    block pays where its scores stay resident, and every block reads the
    gathered rows again, so a step, whose scores are 128 MiB, takes none.
    Since PR 44 a TPU's serving programs do not come here (their absorbed
    core attends through the kernels over live pages and keeps no scores);
    the rule is for what keeps the gather: the expanded form, which
    training runs over its own rows, a replica group's programs, a page
    array that does not lie in whole tiles, a CPU."""
    g = heads
    while g % 2 == 0 and 4 * batch * g * queries * context > _SCORE_BYTES:
        g //= 2
    return g


def _core(cfg, form: str):
    """``core(q, rows, live, w_kb, w_vb)`` of ``form``, :func:`head_block_for`
    heads at a time."""
    prof.inc_counter(f"mla.form.{form}")
    one = functools.partial(CORES[form], scale=softmax_scale(cfg),
                            cdt=jnp.dtype(cfg["compute_dtype"]))

    def core(q, rows, live, w_kb, w_vb):
        B, H, Q, _ = q.shape
        g = head_block_for(B, H, Q, rows.shape[1])
        if g == H:
            return one(q, rows, live, w_kb, w_vb)
        split = lambda w: jnp.moveaxis(w.reshape(w.shape[0], H // g, g, -1), 1, 0)
        out = jax.lax.map(lambda x: one(x[0], rows, live, x[1], x[2]),
                          (jnp.moveaxis(q.reshape(B, H // g, g, Q, -1), 1, 0),
                           split(w_kb), split(w_vb)))  # [H/g, B, g, Q, v]
        return jnp.moveaxis(out, 0, 1).reshape(B, H, Q, -1)

    return core


# -- the three ways the block reaches its cache ----------------------------

def _attend_train(cfg):
    """No cache: every sequence attends over its own rows, expanded."""
    core = _core(cfg, "expanded")

    def attend(i, q, row, w_kb, w_vb):
        T = row.shape[1]
        live = _live_mask(jnp.arange(T), T, None)[None, None]
        return core(q, row, live, w_kb, w_vb)

    return attend


def _attend_pages(cfg, form: str, pages: list, page_tables, pos, page_size: int):
    """Through ``pages[0]``, the engine's one latent page array
    [L, page, offset, row]: ``pos`` [C] with one table [P] (a chunk), or
    [S] with a table a slot [S, P] (a step). The absorbed form brings its
    kernels beside the gathering core; which of the two a program takes is
    ``_paged_attend``'s rule."""
    core = _core(cfg, form)
    width = pages[0].shape[-1]

    def over_pages(asked, gather, live):  # the layer's weights ride with its queries
        q, w_kb, w_vb = asked
        rows = gather(0)
        return core(q, rows.reshape(q.shape[0], -1, width), live[:, 0], w_kb, w_vb)

    zeros_to_width = lambda r: jnp.pad(r, ((0, 0),) * (r.ndim - 1) + ((0, width - r.shape[-1]),))
    paged = _paged_attend(pages, page_tables, pos, page_size, None, core=over_pages,
                          to_row=zeros_to_width,
                          kernels={p: _absorbed_in_kernel(cfg, p) for p in KERNEL_PROGRAMS}
                          if form == "absorbed" else None)
    return lambda i, q, row, w_kb, w_vb: paged(i, (q, w_kb, w_vb), row)


def attends_in_kernel(cfg: dict, pages, page_size: int) -> tuple:
    """``ServingPrograms.attends_in_kernel``: the serving programs that
    attend through a kernel over page arrays shaped as ``pages`` (both run
    absorbed), by ``_paged_attend``'s rule with the row as the one head."""
    fits = step_attends_in_kernel(pages, page_size, pages.shape[-1], None)
    return KERNEL_PROGRAMS if fits else ()


# -- the block, written once -----------------------------------------------

def block(p, x, i: int, cfg: dict, rope, attend, loads: list, routed=None, kernel=None):
    """Layer ``i`` on the float32 residual stream ``x`` [N, T, d_model].
    ``p(name)`` yields a parameter; ``rope`` is the (cos, sin) of the
    tokens' positions, broadcastable to [N, heads, T, rope / 2];
    ``attend(i, q, row, w_kb, w_vb)`` (q [N, H, T, nope + rope], row
    [N, T, rank + rope], without the cache's zeros) returns the heads'
    outputs [N, H, T, v] by whichever form the caller's cache calls for. An
    expert layer appends the tokens each held expert took ([count] int32)
    to ``loads``. ``routed`` [N * T] bool: the tokens whose pairs the expert
    layer computes (None: all); the others' land on no expert, and only the
    shared expert sees them. ``kernel`` is ``ops.moe.expert_share_ffn``'s."""
    N, T, _ = x.shape
    H, nope, rank = cfg["num_heads"], cfg["qk_nope_dim"], cfg["kv_lora_rank"]
    proj, norm, ffn = _ops(p, cfg)
    a = f"layer_{i}/attn"
    with jax.named_scope("latent_attention"):
        n = norm(x, f"layer_{i}/attn_norm")
        q = norm(proj(n, f"{a}/q").reshape(N, T, H, -1).transpose(0, 2, 1, 3), f"{a}/q_norm")
        q = jnp.concatenate([q[..., :nope], apply_rope(q[..., nope:], *rope)], -1)
        kv = proj(n, f"{a}/kv_a")
        row = jnp.concatenate([
            norm(kv[..., :rank], f"{a}/kv_norm"),
            apply_rope(kv[:, None, :, rank:], *rope)[:, 0]], -1)
        w_kvb = p(f"{a}/kv_b/w").reshape(rank, H, -1)
        o = attend(i, q, row, w_kvb[..., :nope], w_kvb[..., nope:])
        x = x + proj(o.transpose(0, 2, 1, 3).reshape(N, T, -1), f"{a}/out")
    with jax.named_scope("ffn"):
        n = norm(x, f"layer_{i}/ffn_norm")
        if i < cfg["first_dense"]:
            return x + ffn(n, i)
        m = f"layer_{i}/moe"
        flat = n.reshape(N * T, -1)
        route = moe.sigmoid_route(flat, p(f"{m}/router/w"), p(f"{m}/router/b"),
                                  cfg["experts_per_token"], cfg["routed_scaling"], routed)
        y, load = moe.expert_share_ffn(
            flat, route, {w: p(f"{m}/experts/{w}/w") for w in ("gate", "fc1", "fc2")},
            held_experts(cfg), compute_dtype=cfg["compute_dtype"], kernel=kernel,
            rows_an_expert=N * T * cfg["experts_per_token"] / cfg["num_experts"])
        loads.append(load)
        # the shared expert: the dense layers' SwiGLU under this layer's names
        return x + y.reshape(N, T, -1) + ffn(n, f"{i}/moe/shared")


def _hidden(p, ids, cfg, rope, attend, **experts):
    """[N, T] token ids -> ([N, T, d_model] after the last block, the expert
    layers' loads [expert layers, count] int32). ``experts``: :func:`block`'s
    ``routed`` and ``kernel``."""
    x, loads = _embed(p, ids), []
    for i in range(cfg["n_layers"]):
        x = block(p, x, i, cfg, rope, attend, loads, **experts)
    count = held_experts(cfg)[1]
    return x, (jnp.stack(loads) if loads else jnp.zeros((0, count), jnp.int32))


def _rope(cfg, t: int, pos0=0):
    return rope_tables(cfg["qk_rope_dim"], t, cfg["rope_theta"], pos0, cfg["rope_scaling"])


# -- parameters -------------------------------------------------------------

def param_shapes(cfg: dict) -> dict:
    """{name: shape} of every parameter; leaves are named ``w``, ``b`` (the
    router's selection bias, beside the router's ``w``), ``scale`` and
    ``word_emb``. The held experts' matrices are stacked in their order:
    ``experts/gate/w`` and ``experts/fc1/w`` [count, d, f], ``experts/fc2/w``
    [count, f, d]."""
    d, H = cfg["d_model"], cfg["num_heads"]
    nope, rope, v, rank = (cfg[k] for k in ("qk_nope_dim", "qk_rope_dim", "v_head_dim",
                                            "kv_lora_rank"))
    swiglu = lambda pfx, f: {f"{pfx}/fc1/w": (d, f), f"{pfx}/gate/w": (d, f),
                             f"{pfx}/fc2/w": (f, d)}
    count, fm = held_experts(cfg)[1], cfg["moe_d_inner"]
    out = {"emb/word_emb": (cfg["vocab"], d), "final_norm/scale": (d,),
           "head/w": (d, cfg["vocab"])}
    for i in range(cfg["n_layers"]):
        a = f"layer_{i}/attn"
        out.update({
            f"layer_{i}/attn_norm/scale": (d,), f"layer_{i}/ffn_norm/scale": (d,),
            f"{a}/q/w": (d, H * (nope + rope)), f"{a}/q_norm/scale": (nope + rope,),
            f"{a}/kv_a/w": (d, rank + rope), f"{a}/kv_norm/scale": (rank,),
            f"{a}/kv_b/w": (rank, H * (nope + v)), f"{a}/out/w": (H * v, d),
        })
        if i < cfg["first_dense"]:
            out.update(swiglu(f"layer_{i}/ffn", cfg["d_inner"]))
            continue
        m = f"layer_{i}/moe"
        out.update(swiglu(f"layer_{i}/moe/shared/ffn", fm))
        out.update({f"{m}/router/w": (d, cfg["num_experts"]),
                    f"{m}/router/b": (cfg["num_experts"],),
                    f"{m}/experts/gate/w": (count, d, fm), f"{m}/experts/fc1/w": (count, d, fm),
                    f"{m}/experts/fc2/w": (count, fm, d)})
    return out


def stack_experts(params: dict, cfg: dict) -> dict:
    """The parameters :func:`param_shapes` names from a checkpoint that
    holds a matrix an expert, ``layer_<i>/moe/experts/<e>/<gate|fc1|fc2>/w``
    with ``e`` the expert's index in the router's width: the held experts'
    are stacked in their order, every other leaf is passed on. ``params`` is
    emptied as it is read, so that the per-expert arrays go as their stacks
    come (at the published sizes they do not fit beside each other twice)."""
    return moe.stack_experts(params, held_experts(cfg))


# -- training ---------------------------------------------------------------

def lm_forward(ids, labels, *, cfg):
    """Next-token training forward: expanded attention, the XLA form of the
    expert layer (its ragged dot differentiates), the router's selection
    bias held constant. ``(loss, token count, logits)``."""
    from paddle_tpu import initializer as init

    shapes = param_shapes(cfg)
    # a stacked leaf is initialised by one expert's own fans, not the stack's
    own = {n: init.Xavier(fan_in=s[1], fan_out=s[2]) for n, s in shapes.items() if len(s) == 3}
    p = _frame_params(cfg, shapes, own)
    x, _ = _hidden(p, ids, cfg, _rope(cfg, ids.shape[1]), _attend_train(cfg), kernel=False)
    return _next_token_loss(_logits(p, x, cfg), labels)


# -- serving: the engine's two programs ------------------------------------

def latent_cache_specs(cfg: dict, *, num_pages: int, page_size: int, dtype, **_):
    """The one page array the engine allocates: a row a position and layer,
    :func:`row_width` wide."""
    return (jax.ShapeDtypeStruct(
        (cfg["n_layers"], num_pages, page_size, row_width(cfg)), dtype),)


def latent_prefill_chunk(params, tokens, pos0, last_index, page_table, latent_pages,
                         rng=None, *, cfg: dict, page_size: int, temperature: float = 0.0,
                         top_k: int | None = None, top_p: float | None = None,
                         form: str = "absorbed"):
    """Prefill ONE sequence's chunk into its pages: ``tokens`` [C] at
    positions ``[pos0, pos0 + C)`` through ``page_table`` [P], as
    ``transformer_lm.paged_prefill_chunk``; the positions past chunk index
    ``last_index`` are padding and reach no routed expert. Returns
    ``(next_token, latent_pages, expert_load)``.

    ``form`` is the attention core's. Absorbed, on a TPU, the chunk's
    queries attend through ``latent_attend_chunk`` over the pages the
    sequence holds (the chunk's own rows are written first and read back
    like any others), sixteen queries under every head a tile; nothing is
    gathered and no score is kept (PERF.md, PR 44). Where the gather stays
    (``_paged_attend``'s rule) both forms score all of the table's
    positions: by operations expanded wins from a few hundred queries on
    (expanding costs T * rank * H * (nope + v) once, absorbing C * T * H *
    (2 * rank - nope - v) more), yet on the chip, at 512 queries over 16384
    gathered rows, absorbed was faster (74.5 against 99.5 ms a chunk,
    PERF.md PR 31): both were bound by the scores they materialised.
    ``tools/moe_gmm_sweep.py`` times all three."""
    _enforce_sampling(temperature, rng, "latent decode")
    p = _dict_params(params)
    (C,) = tokens.shape
    pages = [latent_pages]
    at = jnp.arange(C, dtype=jnp.int32)
    attend = _attend_pages(cfg, form, pages, page_table, pos0 + at, page_size)
    x, load = _hidden(p, tokens[None], cfg, _rope(cfg, C, pos0), attend,
                      routed=at <= last_index)
    x_last = jax.lax.dynamic_index_in_dim(x[0], last_index, 0)
    with jax.named_scope("sampling"):
        tok = sample_logits(_logits(p, x_last, cfg)[0], rng, temperature, top_k, top_p)
    return tok, pages[0], load


def latent_decode_step(params, tokens, positions, page_tables, latent_pages, rng=None,
                       *, cfg: dict, page_size: int, temperature: float = 0.0,
                       top_k: int | None = None, top_p: float | None = None):
    """One decode iteration for ``S`` slots, absorbed: as
    ``transformer_lm.paged_decode_step``. A slot that is idle or still
    prefilling has a scratch table row and position 0 (a decoding slot
    writes a position past its prompt, so never 0): its token reaches no
    routed expert and its output is garbage the engine ignores. Returns
    ``(next_tokens [S], latent_pages, expert_load)``."""
    _enforce_sampling(temperature, rng, "latent decode")
    p = _dict_params(params)
    cos, sin = jax.vmap(lambda at: _rope(cfg, 1, at))(positions)
    pages = [latent_pages]
    attend = _attend_pages(cfg, "absorbed", pages, page_tables, positions, page_size)
    x, load = _hidden(p, tokens[:, None], cfg, (cos[:, None], sin[:, None]), attend,
                      routed=positions > 0)
    with jax.named_scope("sampling"):
        nxt = sample_logits(_logits(p, x[:, 0], cfg), rng, temperature, top_k, top_p)
    return nxt, pages[0], load


def span_attrs(cfg: dict, expert_load: np.ndarray) -> dict:
    """What a call's ``expert_load`` [expert layers, count] says, as the
    attributes its span carries."""
    return {"moe_pairs": int(expert_load.sum()),
            "moe_experts_hit": int(np.count_nonzero(expert_load)),
            "moe_max_load": int(expert_load.max(initial=0))}


def serving_programs() -> ServingPrograms:
    return ServingPrograms(
        cache="pages", cache_args=("latent_pages",), cache_specs=latent_cache_specs,
        prefill_chunk=latent_prefill_chunk, decode_step=latent_decode_step,
        verify_step=None, attends_in_kernel=attends_in_kernel,
        mechanism="latent attention: one page array whose row is a latent "
                  "and a shared rotary key, not a K and a V per head",
        extras=("expert_load",), span_attrs=span_attrs,
        gauges=lambda cfg: {"moe.experts_held": held_experts(cfg)[1],
                            "moe.router_width": cfg["num_experts"]})


# -- registry ---------------------------------------------------------------

def get_model(seq_len: int = 1024, learning_rate: float = 1e-3, **overrides) -> ModelSpec:
    cfg = dict(BASE_CFG)
    cfg.update({k: v for k, v in overrides.items() if k in cfg})
    cfg["max_len"] = max(cfg["max_len"], seq_len)
    first, count = held_experts(cfg)
    enforce(0 <= first and first + count <= cfg["num_experts"] and count >= 1,
            f"experts_held {cfg['experts_held']} is not a range of the "
            f"router's {cfg['num_experts']} experts")
    enforce(0 <= cfg["first_dense"] <= cfg["n_layers"],
            f"first_dense {cfg['first_dense']} of {cfg['n_layers']} layers")
    model = pt.build(functools.partial(lm_forward, cfg=cfg), name="latent_moe_lm")

    def synth_batch(batch_size: int, rng: np.random.RandomState):
        tok = rng.randint(1, cfg["vocab"], size=(batch_size, seq_len + 1)).astype(np.int32)
        return tok[:, :-1], tok[:, 1:]

    return ModelSpec(
        name="latent_moe_lm", model=model, synth_batch=synth_batch,
        optimizer=lambda: pt.optimizer.Adam(learning_rate=learning_rate),
        unit="tokens/sec", examples_per_row=seq_len,
        extra={"cfg": cfg, "seq_len": seq_len})
