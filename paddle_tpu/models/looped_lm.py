"""Decoder-only LM whose whole layer stack runs several passes over the same
weights (Zhu et al. 2025, "Scaling Latent Reasoning via Looped Language
Models"; the published ``ouro`` configs: ``total_ut_steps`` passes,
``early_exit_threshold``).

``x_0 = E[token]`` (not scaled). Pass ``r`` of ``R = total_ut_steps`` is the
whole stack, the same weights in every pass: for layer ``i`` of ``L``,
sandwich norms (an RMSNorm before and after each sublayer, four a layer)::

    h = x + N2_i(Attn_i(N1_i(x); r))        x = h + N4_i(MLP_i(N3_i(h)))

then ``x = Norm_f(x)``: the final RMSNorm closes *every* pass, so the next
pass starts from a normed stream. ``Attn_i``: bias-free q, k, v of
``num_heads`` heads of ``head_dim`` (``num_kv_heads`` of them for k and v),
RoPE on q and k (base ``rope_theta``), causal softmax, ``W_o``. ``MLP_i`` is
SwiGLU. **Pass ``r`` attends over the keys and values pass ``r`` made** at the
earlier positions: a token leaves ``R * L`` K rows and as many V rows behind,
and the cache's plane of (pass ``r``, layer ``i``) is ``r * L + i``. The
cache's planes are therefore not the model's layers.

After each pass the exit gate reads the normed stream, ``lambda_r =
sigmoid(w_g . x + b_g)``. The exit distribution is ``p_r = lambda_r *
prod_{s<r} (1 - lambda_s)``, the last pass taking the remainder; a token
would leave at the first pass whose cumulative ``p`` reaches
``early_exit_threshold``. At the published 1 that is always the last pass:
the logits are ``W_head x`` after pass ``R - 1`` (an untied head), the gate
changes no served token, and it is computed and reported (:func:`exit_cdf`).
A pass count that differs by token is not built (ROADMAP M9).

The block is written once, :func:`block`; training, a prefill chunk and a
decode step differ only in the ``attend`` they hand it. The passes are one
traced body (a ``lax.scan`` over ``r`` with the weights closed over and the
cache in the carry), so a program's size does not grow with ``R``. The
``L`` layers inside it are a second scan over their parameters, held stacked
(``layers/<suffix>`` ``[L, ...]``: what ``get_model`` makes and the programs
read), the traced layer index reaching the pages as part of the plane: one
form, whoever calls. A checkpoint that holds a leaf a layer
(``layer_<i>/<suffix>``) is stacked once, at load (:func:`stack_layers`).
Training (``pt.Trainer``) is cross-entropy on the last pass's logits.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

import paddle_tpu as pt
from paddle_tpu.core.enforce import enforce
from paddle_tpu.models import ModelSpec, ServingPrograms
from paddle_tpu.models.retention_lm import (
    _embed, _enforce_sampling, _frame_params, _next_token_loss, _ops,
)
from paddle_tpu.models.transformer_lm import (
    _attend_cached, _live_mask, _paged_attend, kv_attends_in_kernel, kv_heads, sample_logits,
)
from paddle_tpu.ops.attention import apply_rope, rope_tables

__all__ = [
    "BASE_CFG", "block", "exit_cdf", "exit_pass", "get_model", "lm_forward",
    "looped_cache_specs", "looped_decode_step", "looped_prefill_chunk", "param_shapes",
    "planes", "serving_programs", "span_attrs", "stack_layers",
]

BASE_CFG = dict(
    family="looped_lm",
    vocab=32000,
    d_model=512,
    d_inner=1536,
    num_heads=8,
    num_kv_heads=None,  # < num_heads -> grouped-query attention
    head_dim=64,
    n_layers=4,
    total_ut_steps=4,          # passes of the whole stack
    early_exit_threshold=1.0,  # 1: every token takes every pass
    max_len=2048,
    rope_theta=1e6,
    rms_eps=1e-6,
    # the published checkpoint is bfloat16; so are the held parameters and
    # the matmul operands. Residual stream, norms, RoPE, softmax: float32
    param_dtype="bfloat16",
    compute_dtype="bfloat16",
)

STACKED = "layers/"  # a layer's leaves, stacked: ``layers/<suffix>`` [L, ...]
_LAYER = "layer_0/"  # what the shared ops call a layer's parameter by


def planes(cfg: dict) -> int:
    """Planes of the cache: one a (pass, layer)."""
    return cfg["total_ut_steps"] * cfg["n_layers"]


def _plane(r, i, n_layers: int):
    """The cache plane pass ``r`` writes and attends in layer ``i``."""
    return r * n_layers + i


# -- the block, written once -----------------------------------------------

def block(lp, x, cfg: dict, rope, attend):
    """One layer on the float32 residual stream ``x`` [N, T, d_model].
    ``lp(suffix)`` yields the layer's parameter (``attn/q/w``, ...); ``rope``
    is the (cos, sin) of the tokens' positions, broadcastable to [N, heads,
    T, dh / 2]; ``attend(q, k, v)`` (q [N, H, T, dh], k and v [N, H_kv, T,
    dh], q and k rotated) returns the context [N, H, T, dh] against whatever
    cache the caller keeps for this pass and layer. The flat q, k and v stand
    behind a barrier: without it XLA sinks the split into heads from q's and
    k's result onto their weight, and copies both stacks transposed every call."""
    N, T, _ = x.shape
    dh = cfg["head_dim"]
    proj, norm, ffn = _ops(lambda name: lp(name[len(_LAYER):]), cfg)
    a = _LAYER + "attn"
    heads = lambda y: y.reshape(N, T, -1, dh).transpose(0, 2, 1, 3)
    with jax.named_scope("attention"):
        n = norm(x, _LAYER + "attn_norm")
        q, k, v = map(heads, jax.lax.optimization_barrier(
            tuple(proj(n, f"{a}/{w}") for w in "qkv")))
        ctx = attend(apply_rope(q, *rope), apply_rope(k, *rope), v)
        o = proj(ctx.transpose(0, 2, 1, 3).reshape(N, T, -1), f"{a}/out")
        x = x + norm(o, _LAYER + "attn_post_norm")
    with jax.named_scope("ffn"):
        return x + norm(ffn(norm(x, _LAYER + "ffn_norm"), 0), _LAYER + "ffn_post_norm")


def _hidden(params: dict, ids, cfg: dict, rope, attend, cache: list):
    """[N, T] token ids -> (the normed stream after the last pass [N, T,
    d_model], the exit gate after each pass [R, N, T] float32).
    ``attend(plane, q, k, v)`` reads and rebinds ``cache`` (the list of page
    arrays; empty in training), which is carried through both loops."""
    enforce(STACKED + "attn/q/w" in params,
            "looped_lm holds its layers stacked (layers/<suffix> [L, ...]); a "
            "checkpoint that holds a leaf a layer is stacked once, at load, "
            "by looped_lm.stack_layers")
    p, L = params.__getitem__, cfg["n_layers"]
    stacked = {n[len(STACKED):]: w for n, w in params.items() if n.startswith(STACKED)}
    _, norm, _ = _ops(p, cfg)
    w_g = p("exit_gate/w").astype(jnp.float32)
    b_g = p("exit_gate/b").astype(jnp.float32)

    def one_pass(carry, r):
        def one_layer(carry, sl):
            y, *cache[:] = carry
            y = block(sl["p"].__getitem__, y, cfg, rope,
                      functools.partial(attend, _plane(r, sl["i"], L)))
            return (y, *cache), None

        with jax.named_scope("pass"):
            (x, *cache[:]), _ = jax.lax.scan(one_layer, carry,
                                             {"p": stacked, "i": jnp.arange(L)})
            x = norm(x, "final_norm")
        with jax.named_scope("exit_gate"):
            lam = jax.nn.sigmoid(jnp.matmul(
                x, w_g, precision=jax.lax.Precision.HIGHEST)[..., 0] + b_g[0])
        return (x, *cache), lam

    (x, *cache[:]), lam = jax.lax.scan(
        one_pass, (_embed(p, ids), *cache), jnp.arange(cfg["total_ut_steps"]))
    return x, lam


def _logits(params: dict, x, cfg: dict):
    """The head on the stream the last pass's final norm closed."""
    proj, _, _ = _ops(params.__getitem__, cfg)
    with jax.named_scope("head"):
        return proj(x, "head")


# -- the exit distribution --------------------------------------------------

def exit_cdf(lam):
    """[R, ...] exit gates -> [..., R] cumulative exit distribution:
    ``cdf_r = 1 - prod_{s<=r} (1 - lambda_s)``, the last pass taking the
    remainder (``cdf_{R-1} = 1``)."""
    stay = jnp.cumprod(1.0 - lam, axis=0)
    return jnp.moveaxis(jnp.concatenate([1.0 - stay[:-1], jnp.ones_like(stay[:1])]), 0, -1)


def exit_pass(cdf, threshold: float):
    """[..., R] -> [...] int: the first pass whose cumulative exit
    probability reaches ``threshold``; at 1 that is the last pass unless a
    gate saturates."""
    return np.argmax(np.asarray(cdf) >= threshold, axis=-1)


# -- parameters -------------------------------------------------------------

def _layer_shapes(cfg: dict) -> dict:
    """{suffix: shape} of one layer's parameters."""
    d, f, dh = cfg["d_model"], cfg["d_inner"], cfg["head_dim"]
    H, Hkv = cfg["num_heads"], kv_heads(cfg)
    out = {f"{n}/scale": (d,) for n in ("attn_norm", "attn_post_norm", "ffn_norm",
                                        "ffn_post_norm")}
    out.update({"attn/q/w": (d, H * dh), "attn/k/w": (d, Hkv * dh), "attn/v/w": (d, Hkv * dh),
                "attn/out/w": (H * dh, d),
                "ffn/fc1/w": (d, f), "ffn/gate/w": (d, f), "ffn/fc2/w": (f, d)})
    return out


def param_shapes(cfg: dict) -> dict:
    """{name: shape} of every parameter, the layers' stacked: ``n_layers``
    layers' worth whatever ``total_ut_steps`` is. Leaves are named ``w``,
    ``b`` (the exit gate's), ``scale`` and ``word_emb``."""
    d, L = cfg["d_model"], cfg["n_layers"]
    out = {"emb/word_emb": (cfg["vocab"], d), "final_norm/scale": (d,),
           "head/w": (d, cfg["vocab"]), "exit_gate/w": (d, 1), "exit_gate/b": (1,)}
    out.update({STACKED + n: (L,) + s for n, s in _layer_shapes(cfg).items()})
    return out


def stack_layers(params: dict, cfg: dict) -> dict:
    """The parameters :func:`param_shapes` names from a checkpoint that holds
    a leaf a layer, ``layer_<i>/<suffix>``; every other leaf is passed on.
    ``params`` is emptied as it is read, so that a layer's arrays go as their
    stack comes."""
    out = {}
    for n in _layer_shapes(cfg):
        out[STACKED + n] = jnp.stack(
            [params.pop(f"layer_{i}/{n}") for i in range(cfg["n_layers"])])
    out.update(params)
    params.clear()
    return out


# -- training ---------------------------------------------------------------

def lm_forward(ids, labels, *, cfg):
    """Next-token training forward, every pass over the same parameters,
    cross-entropy on the last pass's logits: ``(loss, token count,
    logits)``. The report's loss over the exit distribution is not built."""
    from paddle_tpu import initializer as init

    shapes = param_shapes(cfg)
    # a stacked matrix is initialised by one layer's own fans, not the stack's
    own = {n: init.Xavier(fan_in=s[1], fan_out=s[2]) for n, s in shapes.items() if len(s) == 3}
    p = _frame_params(cfg, shapes, own)
    params = {n: p(n) for n in shapes}  # made outside the passes' scan
    T = ids.shape[1]
    live = _live_mask(jnp.arange(T), T, None)[None, None, None]

    def attend(plane, q, k, v):
        return _attend_cached(q, k, v, live)

    x, _ = _hidden(params, ids, cfg, rope_tables(cfg["head_dim"], T, cfg["rope_theta"]),
                   attend, [])
    return _next_token_loss(_logits(params, x, cfg), labels)


# -- serving: the engine's two programs ------------------------------------

def looped_cache_specs(cfg: dict, *, num_pages: int, page_size: int, dtype, **_):
    """The K and the V page array the engine allocates: a plane a (pass,
    layer), a row of all heads a position."""
    shape = (planes(cfg), num_pages, page_size, kv_heads(cfg) * cfg["head_dim"])
    return (jax.ShapeDtypeStruct(shape, dtype),) * 2


def looped_prefill_chunk(params, tokens, pos0, last_index, page_table, k_pages, v_pages,
                         rng=None, *, cfg: dict, page_size: int, temperature: float = 0.0,
                         top_k: int | None = None, top_p: float | None = None):
    """Prefill ONE sequence's chunk into its pages, every pass into planes of
    its own: ``tokens`` [C] at positions ``[pos0, pos0 + C)`` through
    ``page_table`` [P], as ``transformer_lm.paged_prefill_chunk``. Returns
    ``(next_token, k_pages, v_pages, exit_cdf [1, R], live_rows [1])``: the
    token sampled at chunk index ``last_index``, the cumulative exit
    distribution there, and the positions the chunk's real queries attend."""
    _enforce_sampling(temperature, rng, "looped decode")
    params = getattr(params, "params", params)
    (C,) = tokens.shape
    pages = [k_pages, v_pages]
    attend = _paged_attend(pages, page_table, pos0 + jnp.arange(C, dtype=jnp.int32),
                           page_size, None)
    rope = rope_tables(cfg["head_dim"], C, cfg["rope_theta"], pos0)
    x, lam = _hidden(params, tokens[None], cfg, rope, attend, pages)
    last = jnp.minimum(last_index, C - 1)  # the engine counts it from the chunk's start
    x_last = jax.lax.dynamic_index_in_dim(x[0], last, 0)
    with jax.named_scope("sampling"):
        tok = sample_logits(_logits(params, x_last, cfg)[0], rng, temperature, top_k, top_p)
    cdf = exit_cdf(jax.lax.dynamic_index_in_dim(lam[:, 0], last, 1))
    return tok, *pages, cdf, (pos0 + last + 1).astype(jnp.int32).reshape(1)


def looped_decode_step(params, tokens, positions, page_tables, k_pages, v_pages, rng=None,
                       *, cfg: dict, page_size: int, temperature: float = 0.0,
                       top_k: int | None = None, top_p: float | None = None):
    """One decode iteration for ``S`` slots, as
    ``transformer_lm.paged_decode_step``, every pass writing and attending
    planes of its own. A slot that is idle or still prefilling has a scratch
    table row and position 0 (a decoding slot writes a position past its
    prompt, so never 0): its output is garbage the engine ignores and its
    ``live_rows`` is 0. Returns ``(next_tokens [S], k_pages, v_pages,
    exit_cdf [S, R], live_rows [S])``."""
    _enforce_sampling(temperature, rng, "looped decode")
    params = getattr(params, "params", params)
    cos, sin = jax.vmap(lambda at: rope_tables(
        cfg["head_dim"], 1, cfg["rope_theta"], at))(positions)
    pages = [k_pages, v_pages]
    attend = _paged_attend(pages, page_tables, positions, page_size, None)
    x, lam = _hidden(params, tokens[:, None], cfg, (cos[:, None], sin[:, None]), attend, pages)
    with jax.named_scope("sampling"):
        nxt = sample_logits(_logits(params, x[:, 0], cfg), rng, temperature, top_k, top_p)
    live_rows = jnp.where(positions > 0, positions + 1, 0).astype(jnp.int32)
    return nxt, *pages, exit_cdf(lam[:, :, 0]), live_rows


def span_attrs(cfg: dict, cdf: np.ndarray, live_rows: np.ndarray) -> dict:
    """What a call's ``exit_cdf`` [slots, R] and ``live_rows`` [slots] say,
    as the attributes its span carries: the passes and planes of the loop,
    the cache rows the call's slots attended (per plane), and the mean over
    those slots of the expected exit pass under ``p_r``, 1-based."""
    on = live_rows > 0
    p = np.diff(cdf[on], axis=-1, prepend=0.0)
    mean_pass = float((p * (1 + np.arange(cdf.shape[-1]))).sum(-1).mean()) if on.any() else 0.0
    return {"loop_passes": cfg["total_ut_steps"], "loop_planes": planes(cfg),
            "exit_mean_pass": mean_pass, "live_rows": int(live_rows.sum())}


def serving_programs() -> ServingPrograms:
    return ServingPrograms(
        cache="pages", cache_args=("k_pages", "v_pages"), cache_specs=looped_cache_specs,
        prefill_chunk=looped_prefill_chunk, decode_step=looped_decode_step,
        verify_step=None,
        mechanism="a decoder whose stack runs several passes, each with K and V "
                  "pages of its own",
        kv_heads=kv_heads, attends_in_kernel=kv_attends_in_kernel,
        extras=("exit_cdf", "live_rows"), span_attrs=span_attrs,
        gauges=lambda cfg: {"loop.passes": cfg["total_ut_steps"], "loop.planes": planes(cfg)})


# -- registry ---------------------------------------------------------------

def get_model(seq_len: int = 1024, learning_rate: float = 1e-3, **overrides) -> ModelSpec:
    cfg = dict(BASE_CFG)
    cfg.update({k: v for k, v in overrides.items() if k in cfg})
    cfg["max_len"] = max(cfg["max_len"], seq_len)
    enforce(cfg["total_ut_steps"] >= 1, f"total_ut_steps {cfg['total_ut_steps']} < 1")
    enforce(cfg["early_exit_threshold"] >= 1.0,
            f"looped_lm: early_exit_threshold {cfg['early_exit_threshold']} < 1 asks for "
            "a pass count that differs by token, which is not built: every token "
            "takes all total_ut_steps passes")
    enforce(cfg["num_heads"] % kv_heads(cfg) == 0,
            f"num_heads {cfg['num_heads']} is not a multiple of num_kv_heads {kv_heads(cfg)}")
    model = pt.build(functools.partial(lm_forward, cfg=cfg), name="looped_lm")

    def synth_batch(batch_size: int, rng: np.random.RandomState):
        tok = rng.randint(1, cfg["vocab"], size=(batch_size, seq_len + 1)).astype(np.int32)
        return tok[:, :-1], tok[:, 1:]

    return ModelSpec(
        name="looped_lm", model=model, synth_batch=synth_batch,
        optimizer=lambda: pt.optimizer.Adam(learning_rate=learning_rate),
        unit="tokens/sec", examples_per_row=seq_len,
        extra={"cfg": cfg, "seq_len": seq_len})
