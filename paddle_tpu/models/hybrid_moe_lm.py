"""Decoder-only LM whose layers are one mixer each, of three kinds from a
pattern string: Mamba-2 layers that keep a recurrent state, attention layers
that keep keys and values, and expert layers whose routed experts live in a
latent space (NVIDIA's ``nemotron_h`` configs with a latent expert layer,
Nemotron 3 Super: ``hybrid_override_pattern`` says which layer is which,
``M`` Mamba-2, ``*`` attention, ``E`` experts).

Stream ``x_0 = E[token]`` (not scaled). Layer ``i`` is **one mixer alone**,
not a mixer and an FFN::

    x = x + Mixer_i(RMSNorm(x))

and ``logits = W_head RMSNorm(x_L)``, the head untied. No bias but the
convolution's, no position embedding and no rotary (the Mamba-2 layers carry
the order).

**Mamba-2 mixer** and **attention mixer** are ``models/hybrid_ssm_lm.py``'s,
one body for both families (``mamba_mixer``, ``attention_mixer`` and the
three ``via`` that reach the cache: training, a prefill chunk, a decode
step): ``ssm_groups`` groups of ``B`` and ``C``, the gated norm by group, GQA
with a causal softmax of ``q . k / sqrt(head_dim)``. ``cfg`` holds that
module's keys under its names (``layer_types`` is made from ``pattern``).

**Expert mixer** (``E``), ``n`` the normed stream::

    s   = sigmoid(W_r n)                 float32, the router's full width
    sel = the experts_per_token largest of s + b      (b enters the selection only)
    w_e = routed_scaling * s_e / sum_sel s
    l   = W_down n                       d_model -> moe_latent
    E_e(l) = W2_e relu(W1_e l)^2         moe_latent -> moe_d_inner -> moe_latent, no gate
    out = W_up(sum_{e in sel, held} w_e E_e(l)) + W2_s relu(W1_s n)^2

the shared expert at the model's width (``d_model -> shared_d_inner ->
d_model``). ``experts_held`` (first, count) says which experts' weights this
model holds: the sum runs over the selected experts that are held and the
others' terms are left out (``ops/moe.py``: one chip's share of an
expert-parallel layer; ``W_up`` is linear, so the shares' partial sums add up
behind it, with the shared expert counted once). Routing, layout, the grouped
matmuls and the scatter are ``ops.moe.sigmoid_route`` and
``ops.moe.expert_share_ffn`` with the ``relu2`` body, as ``latent_moe_lm``
runs them with the SwiGLU body. The held experts' matrices are stacked in
their order, ``experts/fc1/w`` [count, latent, f] and ``experts/fc2/w``
[count, f, latent]; a checkpoint that holds a matrix an expert is stacked
once, at load (:func:`stack_experts`).

What a sequence keeps is what ``hybrid_ssm_lm``'s keeps: K and V pages of the
attention layers, an SSM state and a convolution tail a slot of the Mamba-2
layers (``ServingPrograms.cache`` ``"pages+state"``, the same four arrays);
an expert layer keeps nothing. Both programs return two small arrays after
the cache, ``active`` and ``expert_load``, and the span of a call carries the
SSM's and the expert layers' counts together.

The multi-token-prediction module of the published model (one attention and
one expert layer that draft the token after next) takes no part in the main
model's forward and is not built: drafting over a model that keeps states
needs a rollback of a slot's state, which the engine does not have.

``ssm_dt_shift``, ``ssm_conv_gain`` and ``attn_q_gain`` are ``hybrid_ssm_lm``'s
constants of a configuration (0, 1, 1 for trained weights).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

import paddle_tpu as pt
from paddle_tpu.core.enforce import enforce
from paddle_tpu.models import ModelSpec, ServingPrograms
from paddle_tpu.models import hybrid_ssm_lm as hm
from paddle_tpu.models import latent_moe_lm as lm
from paddle_tpu.models.hybrid_ssm_lm import ATTENTION, MAMBA, layers_of
from paddle_tpu.models.retention_lm import (
    _embed, _enforce_sampling, _frame_params, _logits, _next_token_loss, _ops,
)
from paddle_tpu.models.transformer_lm import kv_attends_in_kernel, kv_heads, sample_logits
from paddle_tpu.ops import moe

__all__ = [
    "BASE_CFG", "KINDS", "MOE", "block", "expert_mixer", "get_model", "held_experts",
    "hybrid_moe_decode_step", "hybrid_moe_prefill_chunk", "lm_forward", "param_shapes",
    "serving_programs", "span_attrs", "stack_experts",
]

MOE = "moe"
KINDS = {"M": MAMBA, "*": ATTENTION, "E": MOE}  # a pattern's letters

BASE_CFG = dict(
    family="hybrid_moe_lm",
    vocab=32000,
    d_model=256,
    pattern="*EMEM",        # one mixer a layer: M Mamba-2, * attention, E experts
    num_heads=4,
    num_kv_heads=None,      # < num_heads -> grouped-query attention
    head_dim=64,
    ssm_heads=8,
    ssm_head_dim=64,
    ssm_state=128,
    ssm_groups=2,           # groups of B and C
    ssm_conv=4,
    ssm_chunk=128,
    num_experts=16,         # the router's width
    experts_per_token=4,
    experts_held=None,      # (first, count) of the experts held here; None = all
    moe_latent=128,         # the width the routed experts work in
    moe_d_inner=256,        # a routed expert's
    shared_d_inner=512,     # the shared expert's, at the model's width
    routed_scaling=2.5,
    rms_eps=1e-5,
    attention_multiplier=None,  # 1 / sqrt(head_dim)
    ssm_dt_shift=0.0,
    ssm_conv_gain=1.0,
    attn_q_gain=1.0,
    max_len=2048,
    # the published checkpoint is bfloat16; so are the held parameters and
    # the matmul operands. Residual stream, norms, the router, dt, decay, SSM
    # state and the products that read or write it are float32
    param_dtype="bfloat16",
    compute_dtype="bfloat16",
)

held_experts = lm.held_experts


# -- the expert mixer and the block, written once ------------------------------

def expert_mixer(p, n, m: str, cfg: dict, loads: list, routed=None, kernel=None):
    """The expert mixer named ``m`` on the normed stream ``n`` [N, T,
    d_model]. Appends the tokens each held expert took ([count] int32) to
    ``loads``. ``routed`` [N * T] bool: the tokens whose pairs are computed
    (None: all); the others reach the shared expert only. ``kernel`` is
    ``ops.moe.expert_share_ffn``'s."""
    N, T, _ = n.shape
    proj = _ops(p, cfg)[0]
    relu2 = lambda h: jnp.square(jax.nn.relu(h))
    flat = n.reshape(N * T, -1)
    route = moe.sigmoid_route(flat, p(f"{m}/router/w"), p(f"{m}/router/b"),
                              cfg["experts_per_token"], cfg["routed_scaling"], routed)
    with jax.named_scope("latent_down"):
        latent = proj(flat, f"{m}/down")
    y, load = moe.expert_share_ffn(
        latent, route, {w: p(f"{m}/experts/{w}/w") for w in ("fc1", "fc2")},
        held_experts(cfg), compute_dtype=cfg["compute_dtype"], kernel=kernel, body="relu2",
        rows_an_expert=N * T * cfg["experts_per_token"] / cfg["num_experts"])
    loads.append(load)
    with jax.named_scope("latent_up"):
        out = proj(y, f"{m}/up")
    with jax.named_scope("shared_expert"):
        out = out + proj(relu2(proj(flat, f"{m}/shared/fc1")), f"{m}/shared/fc2")
    return out.reshape(N, T, -1)


def block(p, x, i: int, cfg: dict, via, loads: list, **experts):
    """Layer ``i`` on the float32 residual stream ``x`` [N, T, d_model]: its
    one mixer. ``via`` is ``hybrid_ssm_lm``'s (how the layer reaches its
    cache), ``experts`` :func:`expert_mixer`'s ``routed`` and ``kernel``."""
    kind = cfg["layer_types"][i]
    n = _ops(p, cfg)[1](x, f"layer_{i}/norm")
    if kind == MOE:
        return x + expert_mixer(p, n, f"layer_{i}/moe", cfg, loads, **experts)
    j = layers_of(cfg, kind).index(i)  # the layer's plane among its kind
    mixer = hm.attention_mixer if kind == ATTENTION else hm.mamba_mixer
    return x + mixer(p, n, f"layer_{i}/{hm.ATTN_OR_MAMBA[kind]}", j, cfg, via)


def _hidden(p, ids, cfg, via, **experts):
    """[N, T] token ids -> ([N, T, d_model] after the last block, the expert
    layers' loads [expert layers, count] int32)."""
    x, loads = _embed(p, ids), []
    for i in range(len(cfg["layer_types"])):
        x = block(p, x, i, cfg, via, loads, **experts)
    return x, jnp.stack(loads)


# -- parameters -------------------------------------------------------------

def param_shapes(cfg: dict) -> dict:
    """{name: shape} of every parameter; leaves are named ``w``, ``b`` (the
    router's selection bias, the convolution's bias, ``dt_bias``), ``scale``,
    ``bias`` and ``word_emb``, as ``hybrid_ssm_lm``'s and ``latent_moe_lm``'s."""
    d, count = cfg["d_model"], held_experts(cfg)[1]
    lat, f, fs = cfg["moe_latent"], cfg["moe_d_inner"], cfg["shared_d_inner"]
    out = {"emb/word_emb": (cfg["vocab"], d), "final_norm/scale": (d,),
           "head/w": (d, cfg["vocab"])}
    for i, kind in enumerate(cfg["layer_types"]):
        out[f"layer_{i}/norm/scale"] = (d,)
        if kind == ATTENTION:
            out.update(hm.attention_param_shapes(cfg, f"layer_{i}/attn"))
        elif kind == MAMBA:
            out.update(hm.mamba_param_shapes(cfg, f"layer_{i}/mamba"))
        else:
            m = f"layer_{i}/moe"
            out.update({f"{m}/router/w": (d, cfg["num_experts"]),
                        f"{m}/router/b": (cfg["num_experts"],),
                        f"{m}/down/w": (d, lat), f"{m}/up/w": (lat, d),
                        f"{m}/shared/fc1/w": (d, fs), f"{m}/shared/fc2/w": (fs, d),
                        f"{m}/experts/fc1/w": (count, lat, f),
                        f"{m}/experts/fc2/w": (count, f, lat)})
    return out


def stack_experts(params: dict, cfg: dict) -> dict:
    """:func:`param_shapes`' parameters from a checkpoint that holds a matrix
    an expert, ``layer_<i>/moe/experts/<e>/<fc1|fc2>/w`` (``ops.moe.stack_experts``;
    ``params`` is emptied as it is read)."""
    return moe.stack_experts(params, held_experts(cfg))


def _check(cfg: dict) -> None:
    kinds = set(cfg["layer_types"])
    enforce(kinds == {MAMBA, ATTENTION, MOE},
            f"hybrid_moe_lm serves a stack with layers of all three kinds ({''.join(KINDS)} "
            f"in its pattern), got {cfg['pattern']!r}; a stack without expert layers is "
            "hybrid_ssm_lm's")
    hm.check_mixers(cfg, "hybrid_moe_lm")
    first, count = held_experts(cfg)
    enforce(0 <= first and first + count <= cfg["num_experts"] and count >= 1,
            f"experts_held {cfg['experts_held']} is not a range of the "
            f"router's {cfg['num_experts']} experts")


# -- training ---------------------------------------------------------------

def lm_forward(ids, labels, *, cfg):
    """Next-token training forward: the chunked form of the SSM core, full
    attention, the XLA form of the expert layer (its ragged dot
    differentiates), the router's selection bias held constant. ``(loss,
    token count, logits)``."""
    from paddle_tpu import initializer as init

    shapes = param_shapes(cfg)
    # a stacked leaf is initialised by one expert's own fans, not the stack's
    own = {n: init.Xavier(fan_in=s[1], fan_out=s[2]) for n, s in shapes.items() if len(s) == 3}
    own.update(hm.mamba_initializers(shapes))
    p = _frame_params(cfg, shapes, own)
    x, _ = _hidden(p, ids, cfg, hm._via_train(cfg), kernel=False)
    return _next_token_loss(_logits(p, x, cfg), labels)


# -- serving: the engine's two programs ------------------------------------

def hybrid_moe_prefill_chunk(params, tokens, pos0, last_index, slot_ref, k_pages, v_pages,
                             ssm_state, conv_state, rng=None, *, cfg: dict, page_size: int,
                             temperature: float = 0.0, top_k: int | None = None,
                             top_p: float | None = None):
    """``hybrid_ssm_lm.hybrid_prefill_chunk`` with expert layers between: the
    positions past chunk index ``last_index`` are padding and reach no routed
    expert. Returns ``(next_token, k_pages, v_pages, ssm_state, conv_state,
    active [1], expert_load)``."""
    _enforce_sampling(temperature, rng, "hybrid decode")
    page_table, slot = slot_ref
    p = hm._params_of(params)
    (C,) = tokens.shape
    cache = hm._cache_in(k_pages, v_pages, ssm_state, conv_state)
    via = hm._via_chunk(cfg, cache, page_table, slot, pos0, last_index, C, page_size)
    x, load = _hidden(p, tokens[None], cfg, via, routed=jnp.arange(C) <= last_index)
    x_last = jax.lax.dynamic_index_in_dim(x[0], jnp.minimum(last_index, C - 1), 0)
    with jax.named_scope("sampling"):
        tok = sample_logits(_logits(p, x_last, cfg)[0], rng, temperature, top_k, top_p)
    return (tok, *hm._cache_out(cache), jnp.ones((1,), jnp.int32), load)


def hybrid_moe_decode_step(params, tokens, positions, slot_refs, k_pages, v_pages,
                           ssm_state, conv_state, rng=None, *, cfg: dict, page_size: int,
                           temperature: float = 0.0, top_k: int | None = None,
                           top_p: float | None = None):
    """``hybrid_ssm_lm.hybrid_decode_step`` with expert layers between: the
    token of an idle or still-prefilling slot (``active`` 0) reaches no
    routed expert. Returns ``(next_tokens [S], k_pages, v_pages, ssm_state,
    conv_state, active [S], expert_load)``."""
    _enforce_sampling(temperature, rng, "hybrid decode")
    page_tables, active = slot_refs
    p = hm._params_of(params)
    cache = hm._cache_in(k_pages, v_pages, ssm_state, conv_state)
    via = hm._via_step(cfg, cache, page_tables, positions, active, page_size)
    x, load = _hidden(p, tokens[:, None], cfg, via, routed=active != 0)
    with jax.named_scope("sampling"):
        nxt = sample_logits(_logits(p, x[:, 0], cfg), rng, temperature, top_k, top_p)
    return (nxt, *hm._cache_out(cache), active.astype(jnp.int32), load)


def span_attrs(cfg: dict, active: np.ndarray, expert_load: np.ndarray) -> dict:
    """The SSM's counts and the expert layers', on one span."""
    return {**hm.span_attrs(cfg, active), **lm.span_attrs(cfg, expert_load)}


def _gauges(cfg: dict) -> dict:
    layers = len(layers_of(cfg, MAMBA))
    return {"ssm.layers": layers, "ssm.state_bytes_a_slot": layers * hm.state_bytes_a_slot(cfg),
            "moe.experts_held": held_experts(cfg)[1], "moe.router_width": cfg["num_experts"]}


def serving_programs() -> ServingPrograms:
    return ServingPrograms(
        cache="pages+state", cache_args=hm.CACHE_ARGS, state_args=hm.STATE_ARGS,
        cache_specs=hm.hybrid_cache_specs, prefill_chunk=hybrid_moe_prefill_chunk,
        decode_step=hybrid_moe_decode_step, verify_step=None,
        mechanism="Mamba-2 layers with a recurrent state per slot beside attention "
                  "layers with KV pages and latent expert layers",
        kv_heads=kv_heads, attends_in_kernel=kv_attends_in_kernel,
        extras=("active", "expert_load"), span_attrs=span_attrs, gauges=_gauges)


# -- registry ---------------------------------------------------------------

def get_model(seq_len: int = 1024, learning_rate: float = 1e-3, **overrides) -> ModelSpec:
    cfg = dict(BASE_CFG)
    cfg.update({k: v for k, v in overrides.items() if k in cfg})
    enforce(set(cfg["pattern"]) <= set(KINDS),
            f"hybrid_moe_lm: pattern {cfg['pattern']!r} may hold {''.join(KINDS)}")
    cfg["layer_types"] = tuple(KINDS[c] for c in cfg["pattern"])
    cfg["max_len"] = max(cfg["max_len"], seq_len)
    _check(cfg)
    model = pt.build(functools.partial(lm_forward, cfg=cfg), name="hybrid_moe_lm")

    def synth_batch(batch_size: int, rng: np.random.RandomState):
        tok = rng.randint(1, cfg["vocab"], size=(batch_size, seq_len + 1)).astype(np.int32)
        return tok[:, :-1], tok[:, 1:]

    return ModelSpec(
        name="hybrid_moe_lm", model=model, synth_batch=synth_batch,
        optimizer=lambda: pt.optimizer.Adam(learning_rate=learning_rate),
        unit="tokens/sec", examples_per_row=seq_len,
        extra={"cfg": cfg, "seq_len": seq_len})
