"""Decoder-only causal language model (GPT-style) — the long-context
flagship for the flash-attention + bf16 training path.

The reference benchmark suite has no decoder-only config (its transformer
is the NMT encoder-decoder, ``benchmark/fluid/models/transformer.py``);
this model extends the family the TPU-first way: causal masking is
STRUCTURAL (``scaled_dot_product_attention(causal=True)`` → the Pallas
flash kernel skips above-diagonal blocks and never materializes [T, T]),
sequence length is a config knob up to 8k+ (ring attention / seq-axis
sharding take over beyond single-chip VMEM), and matmuls run bf16 under
``flags().use_bf16_compute``.

Sharding: reuses the Megatron-style column/row-parallel projections of
``models/transformer.py`` (q/k/v/fc1 column, out/fc2 row over the model
axis).

Two halves. Training is ``lm_forward`` over ``lm_block`` (the framework's
layers: parameters created in the frame, dropout, MoE, ring / Ulysses /
flash cores, remat, scan, pipeline). Decoding is one ``decode_block`` over
the trained parameters with three caches behind its ``attend``:
``generate``'s static cache, ``generate_beam``'s, and the serving engine's
KV pages (``paged_prefill_chunk`` / ``paged_decode_step`` /
``paged_verify_step`` over one ``_paged_attend``).
"""

from __future__ import annotations

import functools
import types

import jax
import jax.numpy as jnp
import numpy as np

import paddle_tpu as pt
from paddle_tpu import layers
from paddle_tpu.core.enforce import enforce
from paddle_tpu.framework import name_scope
from paddle_tpu.models import ModelSpec
from paddle_tpu.models.transformer import (
    _post_process,
    _proj,
    multi_head_attention,
    positionwise_ffn,
    prepare_embedding,
    sinusoid_position_encoding,
)
from paddle_tpu.ops.attention import apply_rope, rope_tables, scaled_dot_product_attention

__all__ = ["get_model", "lm_forward", "generate", "generate_beam",
           "stack_decode_params", "BASE_CFG",
           "kv_heads", "paged_cache_shape", "paged_prefill_chunk", "paged_decode_step",
           "paged_verify_step"]


def _ring_core(ring_mesh, window=None):
    """Attention core for sequence-parallel long context: exact causal
    attention over the seq-sharded global sequence via the ring
    (``ops/ring_attention.py``) instead of XLA's all-gather lowering."""
    from paddle_tpu.ops.ring_attention import ring_attention_sharded

    return lambda qh, kh, vh, kv_len=None: ring_attention_sharded(
        qh, kh, vh, ring_mesh, causal=True, window=window, kv_len=kv_len
    )


def _ulysses_core(mesh, window=None):
    """All-to-all sequence parallelism (``ops/ulysses.py``): re-shard
    seq->head, plain flash attention on full local sequences, shard back."""
    from paddle_tpu.ops.ulysses import ulysses_attention_sharded

    return lambda qh, kh, vh, kv_len=None: ulysses_attention_sharded(
        qh, kh, vh, mesh, causal=True, window=window, kv_len=kv_len
    )


def _rope_core(cfg):
    """Attention core applying rotary position embeddings to q/k before the
    (flash-routed) fused attention; positions are absolute so scores are
    relative-position functions."""
    def core(qh, kh, vh, kv_len=None):
        cos, sin = rope_tables(qh.shape[-1], qh.shape[-2])
        return scaled_dot_product_attention(
            apply_rope(qh, cos, sin), apply_rope(kh, cos, sin), vh, causal=True,
            window=cfg.get("attention_window"), kv_len=kv_len,
        )

    return core


def _with_rope(core):
    """Wrap a sequence-parallel attention core with RoPE: the rotation is
    per-position (applied on the GLOBAL [B, H, T, d] arrays before the core
    shards them), so rope composes exactly with ring/ulysses."""
    def rotated(qh, kh, vh, kv_len=None):
        cos, sin = rope_tables(qh.shape[-1], qh.shape[-2])
        q_r, k_r = apply_rope(qh, cos, sin), apply_rope(kh, cos, sin)
        return core(q_r, k_r, vh, kv_len=kv_len) if kv_len is not None else core(q_r, k_r, vh)

    return rotated


def lm_block(x, cfg, name, kv_len=None):
    """One decoder block: attention + FFN (dense or mixture-of-experts).
    Returns ``(x, aux_loss)`` — aux is the router load-balance loss when
    ``cfg['moe_experts']`` selects an expert-parallel MoE FFN
    (``parallel/moe.py``), else 0."""
    ring_mesh = cfg.get("ring_mesh")
    ulysses_mesh = cfg.get("ulysses_mesh")
    window = cfg.get("attention_window")
    if ring_mesh is not None:
        core = _ring_core(ring_mesh, window=window)
    elif ulysses_mesh is not None:
        core = _ulysses_core(ulysses_mesh, window=window)
    else:
        core = None
    if cfg.get("pos_encoding") == "rope":
        core = _with_rope(core) if core is not None else _rope_core(cfg)
    with name_scope(name):
        attn = multi_head_attention(
            x, x, x, cfg["d_model"], cfg["num_heads"],
            dropout_rate=cfg["attn_dropout"], causal=True, name="self_attn",
            core=core, num_kv_heads=cfg.get("num_kv_heads"),
            window=cfg.get("attention_window"), kv_len=kv_len,
        )
        x = _post_process(x, attn, cfg["residual_dropout"])
        if cfg.get("moe_experts"):
            from paddle_tpu.parallel.moe import moe_ffn

            # ragged batches: padding tokens are masked out of routing so
            # they consume no expert capacity and don't skew the balance
            token_mask = None
            if kv_len is not None:
                token_mask = (
                    jnp.arange(x.shape[-2])[None, :] < kv_len[:, None]
                )
            with jax.named_scope("ffn"):
                mo = moe_ffn(
                    x, num_experts=cfg["moe_experts"], d_ff=cfg["d_inner"],
                    capacity_factor=cfg.get("moe_capacity_factor", 1.25),
                    router=cfg.get("moe_router", "top1"), name="moe_ffn",
                    token_mask=token_mask,
                )
            ffn, aux = mo.output, mo.aux_loss
        else:
            ffn = positionwise_ffn(
                x, cfg["d_inner"], cfg["d_model"], cfg["relu_dropout"],
                activation=cfg.get("ffn_activation", "relu"),
            )
            aux = jnp.float32(0.0)
        return _post_process(x, ffn, cfg["residual_dropout"]), aux


def _block_caller(cfg):
    """Returns ``call(x, name) -> (x, aux)``; with cfg['remat'] each layer
    runs under jax.checkpoint — activations recompute in backward, so
    training memory scales with ONE layer's activations instead of
    n_layers (the standard long-context trade; transpiler/memory.py holds
    the named-policy variants). cfg/name are closed over (static); the
    framework's trace-time param creation fires inside the checkpointed
    region, which is safe — creation is name-keyed and idempotent across
    the fwd/bwd re-traces."""
    if not cfg.get("remat"):
        return lambda x, name, kv_len=None: lm_block(x, cfg, name, kv_len)

    def call(x, name, kv_len=None):
        # remat only matters for the backward pass: during init the param
        # initializer outputs would leak out of checkpoint's inner trace,
        # and in eval mode checkpoint's CSE barriers are a pure slowdown
        if pt.framework.is_initializing() or not pt.framework.is_training():
            return lm_block(x, cfg, name, kv_len)
        return jax.checkpoint(lambda y: lm_block(y, cfg, name, kv_len))(x)

    return call


def _scan_lm_blocks(x, cfg, seq_lens):
    """Run the layer stack as ONE ``lax.scan`` over stacked per-layer params
    instead of an unrolled Python loop — the canonical TPU pattern: the
    block body appears ONCE in the traced program regardless of depth
    (measured, 12-layer d_model=256 train step: 291 → 27 dot_generals in
    the lowered HLO). That bounds the expensive per-instance TPU kernel
    compilation (each unrolled layer is its own Mosaic flash fwd+bwd
    compile; scanned pays one) and keeps program size flat as n_layers
    grows. On CPU-XLA, where per-op compile is cheap, measured wall-clock
    compile is neutral-to-slightly-slower (scan adds loop/grad machinery)
    — the flag targets the TPU toolchain. Math is identical to the
    unrolled loop; the dropout STREAM differs (per-layer keys are
    pre-split rather than drawn from the frame sequence), so
    seeded-dropout runs are not bit-comparable across the two modes —
    loss statistics are unaffected.

    Mechanics: :func:`framework.scan_layer_stack` — per-layer parameter
    arrays (identical names/shapes across layers by construction) stack to
    [L, ...] pytrees; the scan body re-enters ``lm_block`` under a fresh
    :func:`framework.overlay_frame` mapping ``layer_tpl/...`` to the
    scanned slice. With ``cfg['remat']`` the body runs under
    ``jax.checkpoint`` (scan-of-checkpoint: activation memory O(one
    layer))."""
    return pt.framework.scan_layer_stack(
        x,
        cfg["n_layers"],
        lambda i: f"layer_{i}",
        "layer_tpl",
        lambda h, name: lm_block(h, cfg, name, seq_lens),
        remat=bool(cfg.get("remat")) and pt.framework.is_training(),
        with_aux=True,
    )


def _pipeline_lm_blocks(x, cfg):
    """Run the layer stack pipeline-parallel over cfg['pipe_mesh']'s
    ``pipe`` axis: layers split into n_stages contiguous groups, each pipe
    device owns one group's (stacked) params, and microbatch activations
    flow stage-to-stage through :func:`parallel.pipeline_apply` (GPipe
    schedule by ``ppermute``+``scan``; ``cfg['remat']`` gives the 1F1B
    memory profile). Inside a stage the group runs as a ``lax.scan`` over
    its layers — the same overlay mechanics as
    :func:`framework.scan_layer_stack`. Embedding/projection compute stays
    replicated across pipe ranks (their params are small next to the
    stack). v1 scope: dense batches (``seq_lens`` unsupported) and
    deterministic layers (dropout must be 0 — the pipeline body takes no
    rng stream); both are enforced at dispatch in :func:`lm_forward`.
    """
    from paddle_tpu.parallel.pipeline import pipeline_apply, split_microbatches

    mesh = cfg["pipe_mesh"]
    n_stages = mesh.shape["pipe"]
    L = cfg["n_layers"]
    pt.check(
        L % n_stages == 0,
        f"pipe parallelism needs n_layers ({L}) divisible by the pipe axis "
        f"({n_stages})",
    )
    lps = L // n_stages
    # [S, L/S, ...] per suffix: leading dim shards over the pipe axis
    stacked = {
        s: v.reshape((n_stages, lps) + v.shape[1:])
        for s, v in pt.framework.gather_layer_params(
            L, lambda i: f"layer_{i}"
        ).items()
    }

    def stage_fn(stage_params, h):
        def layer_body(carry, sl):
            overlay = {f"layer_tpl/{s}": v for s, v in sl.items()}
            with pt.framework.overlay_frame(overlay):
                # pipe stages carry activations only; MoE (whose aux loss
                # would be dropped here) is guarded off in lm_forward
                y, _ = lm_block(carry, cfg, "layer_tpl", None)
            return y, None

        h, _ = jax.lax.scan(layer_body, h, stage_params)
        return h

    n_micro = int(cfg.get("pipe_n_micro") or 2 * n_stages)
    mbs = split_microbatches(x, n_micro)
    out = pipeline_apply(
        stage_fn, stacked, mbs, mesh,
        # remat matters only for the backward; in eval it is a pure slowdown
        remat=bool(cfg.get("remat")) and pt.framework.is_training(),
    )
    return out.reshape(x.shape)


def lm_forward(ids, labels, seq_lens=None, *, cfg):
    """Next-token LM training forward: returns (loss, token_count, logits).

    ``ids``/``labels`` are [B, T] int32. ``seq_lens`` ([B] int32, optional)
    marks suffix padding for ragged batches: attention masks key positions
    >= seq_lens[b] structurally (kv_len through the flash kernels — and
    through ring/ulysses when a sequence-parallel mesh is configured), and
    the loss averages only positions p with p < seq_lens[b] - 1 (the last
    real token has no next-token target). Without it every position is a
    target (synthetic data has no padding)."""
    x = prepare_embedding(
        ids, cfg["vocab"], cfg["d_model"], cfg["max_len"],
        cfg["residual_dropout"], name="emb",
        add_position_encoding=cfg.get("pos_encoding", "sinusoid") != "rope",
    )
    if cfg.get("moe_experts"):
        pt.check(
            cfg.get("ffn_activation", "relu") == "relu",
            "moe_experts: expert FFNs are two-layer ReLU; "
            f"ffn_activation={cfg.get('ffn_activation')!r} is not supported "
            "in the MoE path (v1 scope)",
        )
        pt.check(
            not cfg["relu_dropout"],
            "moe_experts: expert FFNs have no dropout; set relu_dropout=0 "
            "(v1 scope)",
        )
    aux_total = jnp.float32(0.0)
    # dispatch precedence: pipe_mesh subsumes scan_layers (each pipe stage
    # already runs its layer group as a lax.scan — see _pipeline_lm_blocks),
    # so setting both is harmless and scan_layers adds nothing under pipe
    if cfg.get("pipe_mesh") is not None and not pt.framework.is_initializing():
        pt.check(
            cfg.get("ring_mesh") is None and cfg.get("ulysses_mesh") is None,
            "pipe_mesh: sequence parallelism (ring_mesh/ulysses_mesh) does "
            "not compose with the pipelined path (v1 scope)",
        )
        pt.check(seq_lens is None,
                 "pipe_mesh: ragged seq_lens unsupported in the pipelined "
                 "path (v1 scope)")
        pt.check(
            not (cfg["attn_dropout"] or cfg["relu_dropout"]
                 or cfg["residual_dropout"]),
            "pipe_mesh: dropout must be 0 (the pipeline body is "
            "deterministic; no rng stream threads through the schedule)",
        )
        pt.check(not cfg.get("moe_experts"),
                 "pipe_mesh: MoE FFNs unsupported in the pipelined path "
                 "(the stage schedule carries activations only, so the "
                 "router aux loss would be dropped)")
        x = _pipeline_lm_blocks(x, cfg)
    elif cfg.get("scan_layers") and not pt.framework.is_initializing():
        # init stays unrolled (trace-time param creation needs the real
        # per-layer names); apply scans — compile time O(1) in n_layers
        x, aux_total = _scan_lm_blocks(x, cfg, seq_lens)
    else:
        block = _block_caller(cfg)
        for i in range(cfg["n_layers"]):
            x, aux = block(x, name=f"layer_{i}", kv_len=seq_lens)
            aux_total = aux_total + aux
    with jax.named_scope("head"):
        x = layers.layer_norm(x, begin_norm_axis=x.ndim - 1)
        with name_scope("project"):
            logits = _proj(x, cfg["vocab"], shard_out=True, name="logits", bias=False)
    with jax.named_scope("loss"):
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    # MoE router load-balance term (0 for dense-FFN configs) — a TRAINING
    # regularizer only: eval loss must stay the pure NLL so perplexity and
    # dense-baseline comparisons are unbiased
    aux_term = (
        jnp.float32(cfg.get("moe_aux_weight", 0.01)) * aux_total
        if pt.framework.is_training()
        else jnp.float32(0.0)
    )
    if seq_lens is not None:
        valid = (jnp.arange(labels.shape[1])[None, :] < seq_lens[:, None] - 1)
        valid = valid.astype(jnp.float32)
        n_tok = jnp.maximum(jnp.sum(valid), 1.0)
        return jnp.sum(nll * valid) / n_tok + aux_term, n_tok, logits
    n_tok = float(np.prod(labels.shape))
    return jnp.mean(nll) + aux_term, n_tok, logits


# ---- the decode side: one block, three caches behind ``attend`` -----------
#
# Decoding runs the trained parameters (names as ``lm_forward`` created them)
# as plain jittable functions over a lookup ``p(name)``. It is deliberately
# NOT built on ``lm_block``: a scan-stepped cache cannot use
# ``multi_head_attention``'s shape-growing concatenate cache, and re-entering
# ``name_scope``s inside a scan body would re-uniquify parameter names
# (ROADMAP C1 names the seam that would join the two). Everything a layer
# does is ``decode_block``; the five entry points differ only in where K and
# V live, which is the ``attend`` each hands the block: generate()'s static
# cache, generate_beam()'s (layer axis behind the beam axis), or the engine's
# pages. ``test_transformer_lm_generate_matches_naive_decode`` pins the block
# to ``lm_forward``, and the serving tests pin the paged path to generate()
# token for token.


def _decode_ffn_fn(proj, swiglu: bool):
    """FFN of the cached decoders (and of ``retention_lm``), pinned to
    ``positionwise_ffn``: relu(fc1) or fc1 * silu(gate)."""
    def ffn(x, i):
        if swiglu:
            h = proj(x, f"layer_{i}/ffn/fc1") * jax.nn.silu(proj(x, f"layer_{i}/ffn/gate"))
        else:
            h = jax.nn.relu(proj(x, f"layer_{i}/ffn/fc1"))
        return proj(h, f"layer_{i}/ffn/fc2")

    return ffn


def _decode_ops(p, cfg):
    """LayerNorm, projection, FFN and output logits over the lookup
    ``p(name)``: the one set of parameter ops of every cached decoder."""
    def ln(x, pfx):
        mu = jnp.mean(x, -1, keepdims=True)
        var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
        return (x - mu) * jax.lax.rsqrt(var + 1e-5) * p(f"{pfx}/scale") + p(f"{pfx}/bias")

    def proj(x, pfx, bias=True):
        out = x @ p(f"{pfx}/w")
        return out + p(f"{pfx}/b") if bias else out

    def logits_of(x_last):  # [..., D] -> [..., vocab]
        return ln(x_last, "layer_norm") @ p("project/logits/w")

    ffn = _decode_ffn_fn(proj, cfg.get("ffn_activation", "relu") == "swiglu")
    return types.SimpleNamespace(p=p, cfg=cfg, ln=ln, proj=proj, ffn=ffn, logits_of=logits_of)


def decode_block(ops, x, i, rotate, attend):
    """Layer ``i`` (an int, or ``"SCAN"`` under the layer scan) on ``x``
    [N, T, D], or [N, D] for one token a sequence: q/k/v projections split
    into heads [N, n, T, dh] (or [N, n, dh]), ``rotate`` on q and k (RoPE at
    the caller's positions, or identity), ``attend(i, q, k, v)`` -> the
    context, shaped as q, against whatever cache the caller keeps (it stores
    k and v there), out-projection, post-LN, FFN, post-LN."""
    dh = x.shape[-1] // ops.cfg["num_heads"]
    pfx = f"layer_{i}/self_attn"
    heads = lambda y: jnp.moveaxis(y.reshape(*x.shape[:-1], -1, dh), -2, 1)
    with jax.named_scope("attention"):
        q, k, v = (heads(ops.proj(x, f"{pfx}/{w}")) for w in "qkv")
        ctx = jnp.moveaxis(attend(i, rotate(q), rotate(k), v), 1, -2).reshape(x.shape)
        x = ops.ln(x + ops.proj(ctx, f"{pfx}/out"), f"layer_{i}/layer_norm")
    with jax.named_scope("ffn"):
        return ops.ln(x + ops.ffn(x, i), f"layer_{i}/layer_norm_1")


def _embed(ops, ids, rows, n_pos: int):
    """ids [N, T] or [N] -> x [..., D]: word embedding x sqrt(D), plus the
    sinusoid table's rows at the tokens' positions unless RoPE puts position
    into the attention rotation instead. ``rows(table)`` picks those rows of
    a [n_pos, .] table: [N, T, .] or [N, .], N being 1 where the sequences
    share their positions."""
    D = ops.cfg["d_model"]
    with jax.named_scope("embed"):
        e = jnp.take(ops.p("emb/embedding/word_emb"), ids, axis=0) * (D ** 0.5)
        if ops.cfg.get("pos_encoding", "sinusoid") == "rope":
            return e
        return e + rows(sinusoid_position_encoding(n_pos, D))


def _rotation(ops, rows, n_pos: int):
    """``rotate(x)`` for q and k [N, n, T, dh] or [N, n, dh]: RoPE at the
    tokens' absolute positions (``rows`` as for :func:`_embed`), the identity
    without RoPE. Cached K is stored PRE-rotated: the rotation depends only
    on the key's own position, and scores only on relative offsets."""
    cfg = ops.cfg
    if cfg.get("pos_encoding", "sinusoid") != "rope":
        return lambda x: x
    cos, sin = (jnp.expand_dims(rows(t), 1)  # the heads' axis
                for t in rope_tables(cfg["d_model"] // cfg["num_heads"], n_pos))
    return lambda x: apply_rope(x, cos, sin)


def _live_mask(q_pos, t_max: int, window):
    """[..., t_max] bool: cache position t is visible from a query at
    position ``q_pos`` ([...] int32) — causal, and within the last ``window``
    positions when sliding. Positions not written yet are > q_pos."""
    t = jnp.arange(t_max)
    q_pos = jnp.asarray(q_pos)[..., None]
    live = t <= q_pos
    if window is not None:
        live &= t > q_pos - window
    return live


def _attend_cached(q, kl, vl, live):
    """Grouped-query softmax attention of q [N, H, Q, dh] over a cache's
    rows kl, vl [N, H_kv, T, dh] under ``live`` (broadcastable to
    [N, 1, 1, Q, T]) -> [N, H, Q, dh]."""
    N, H, Q, dh = q.shape
    H_kv = kl.shape[1]
    qg = q.reshape(N, H_kv, H // H_kv, Q, dh)
    s = jnp.einsum("bkgqd,bktd->bkgqt", qg, kl) * (1.0 / np.sqrt(dh))
    s = jnp.where(live, s, -1e9)
    ctx = jnp.einsum("bkgqt,bktd->bkgqd", jax.nn.softmax(s, -1), vl)
    return ctx.reshape(N, H, Q, dh)


def run_layer_scan(ops, x, rotate, attend, cache: list, scan_view: dict, stacked: dict):
    """The layer loop as one ``lax.scan`` over ``stacked`` ({suffix: [L, ...]},
    see :func:`stack_decode_params`) — compile cost O(1) in depth, the
    decode-side analogue of ``framework.scan_layer_stack``. Each slice
    repopulates ``scan_view``, which the ops' lookup reads under the reserved
    ``layer_SCAN/`` prefix; ``cache`` (the list ``attend`` writes) rides the
    carry, and ``attend`` is handed the traced layer index."""
    def body(carry, sl):
        y, *cache[:] = carry
        scan_view.clear()
        scan_view.update(sl["p"])
        y = decode_block(ops, y, "SCAN", rotate,
                         lambda _, q, k, v: attend(sl["i"], q, k, v))
        return (y, *cache), None

    (x, *cache[:]), _ = jax.lax.scan(
        body, (x, *cache), {"p": stacked, "i": jnp.arange(ops.cfg["n_layers"])})
    return x


def _params_of(variables_or_params):
    return getattr(variables_or_params, "params", variables_or_params)


def stack_decode_params(variables_or_params, cfg: dict) -> dict:
    """Stack the per-layer parameter arrays for ``scan_layers`` decode:
    {suffix: [L, ...]}. Call ONCE outside the jitted decode (or let jit
    close over the result) so the stack is not re-copied per call; pass to
    :func:`generate` as ``stacked_params``."""
    return pt.framework.stack_layer_params(
        _params_of(variables_or_params), cfg["n_layers"], lambda i: f"layer_{i}"
    )


def _static_decoder(variables, cfg, batch: int, t_max: int, layer_axis: int,
                    cache_dtype, stacked_params):
    """What generate() and generate_beam() share: a static k/v cache of
    ``t_max`` positions, [L, B, H_kv, T, dh] or (``layer_axis`` 1: beam
    tiling stays on dim 0) [B, L, H_kv, T, dh], and ``run(ids, t, prefill)``
    that takes ids [B, T] at positions [t, t + T) through every layer.
    Returns ``(ops, cache, run)``; ``cache`` is the list [k, v] that ``run``
    reads and rebinds, so a scan step puts its carry there first.

    With ``cfg['scan_layers']`` the layer loop is :func:`run_layer_scan`;
    prefer a caller-prestacked tree (``stacked_params``, built once OUTSIDE
    jit) — stacking here copies the full parameter set on every jitted call."""
    params = _params_of(variables)
    H, L = cfg["num_heads"], cfg["n_layers"]
    window = cfg.get("attention_window")
    n_pos = max(cfg["max_len"], t_max)
    scan_view: dict = {}
    stacked = None
    if cfg.get("scan_layers"):
        stacked = (stacked_params if stacked_params is not None
                   else stack_decode_params(params, cfg))

    def p(name):
        if name.startswith("layer_SCAN/"):
            return scan_view[name[len("layer_SCAN/"):]]
        return params[name]

    ops = _decode_ops(p, cfg)
    shape = [batch, cfg.get("num_kv_heads") or H, t_max, cfg["d_model"] // H]
    shape.insert(layer_axis, L)  # GQA: the cache holds H_kv heads
    cache = [jnp.zeros(shape, cache_dtype or jnp.float32)] * 2

    def run(ids, t, prefill=False):
        rows = lambda table: jax.lax.dynamic_slice_in_dim(table, t, ids.shape[1], axis=0)[None]

        def attend(li, q, k, v):
            start = [0, 0, 0, t, 0]
            start[layer_axis] = li
            for j, new in enumerate((k, v)):
                cache[j] = jax.lax.dynamic_update_slice(
                    cache[j], jnp.expand_dims(new, layer_axis).astype(cache[j].dtype), start)
            if prefill:
                # sdpa routes long prompts through the flash kernel when the
                # flag is on (no [T, T] materialization) and composes the
                # identical causal+window einsum math otherwise — the
                # training forward's path, so decode-vs-forward stays exact
                return scaled_dot_product_attention(q, k, v, causal=True, window=window)
            kl, vl = (jax.lax.dynamic_index_in_dim(c, li, layer_axis, keepdims=False)
                      for c in cache)
            return _attend_cached(q, kl, vl, _live_mask(t, t_max, window))

        x, rotate = _embed(ops, ids, rows, n_pos), _rotation(ops, rows, n_pos)
        if stacked is not None:
            return run_layer_scan(ops, x, rotate, attend, cache, scan_view, stacked)
        for i in range(L):
            x = decode_block(ops, x, i, rotate, attend)
        return x

    return ops, cache, run


def generate(
    variables,
    prompt: jax.Array,
    max_new_tokens: int,
    cfg: dict,
    temperature: float = 0.0,
    rng: jax.Array | None = None,
    top_k: int | None = None,
    top_p: float | None = None,
    cache_dtype=None,
    stacked_params: dict | None = None,
) -> jax.Array:
    """Autoregressive decode with a static k/v cache — prefill once over the
    prompt, then one ``lax.scan`` step per new token (single compile, no
    shape growth; the TPU-idiomatic replacement for the reference's
    per-step re-run of a decode program). Returns [B, max_new_tokens] int32.
    Greedy at ``temperature=0``, else :func:`sample_logits` with ``rng``
    (required then). ``cfg['scan_layers']`` runs the layer loop, prefill and
    per token, as a ``lax.scan`` over stacked params (``stacked_params``
    from :func:`stack_decode_params` avoids re-stacking per jitted call).

    ``cache_dtype`` (default f32): the k/v cache dtype. ``jnp.bfloat16``
    halves decode HBM traffic — the decode-throughput lever on TPU, where
    each step streams the whole cache — at bf16 rounding of cached keys/
    values (scores still accumulate f32; confident predictions are
    unaffected, see the memorized-decode test).
    """
    B, Tp = prompt.shape
    enforce(max_new_tokens >= 1, f"max_new_tokens must be >= 1, got {max_new_tokens}")
    enforce(
        temperature == 0.0 or rng is not None,
        "generate: sampling (temperature > 0) needs an explicit rng key — "
        "a silent fixed default would return identical 'samples' every call",
    )
    enforce(
        not cfg.get("moe_experts"),
        "generate: MoE FFNs are not supported in the cached decoders yet — "
        "decode with lm_forward teacher-forcing, or use a dense-FFN config",
    )
    ops, cache, run = _static_decoder(
        variables, cfg, B, Tp + max_new_tokens, 0, cache_dtype, stacked_params)
    sample = functools.partial(sample_logits, temperature=temperature,
                               top_k=top_k, top_p=top_p)

    # ---- prefill: full causal pass over the prompt fills caches [0, Tp)
    x = run(prompt, 0, prefill=True)
    first_key, scan_rng = (
        jax.random.split(rng) if rng is not None else (None, None)
    )
    first_tok = sample(ops.logits_of(x[:, -1]), first_key)

    # ---- decode: one token per scan step against the cache
    def step(carry, s):
        tok, *cache[:], key = carry
        y = run(tok[:, None], Tp + s)  # [B, 1, D]
        if key is not None:
            key, sub = jax.random.split(key)
        else:
            sub = None
        nxt = sample(ops.logits_of(y[:, -1]), sub)
        return (nxt, *cache, key), tok

    if max_new_tokens == 1:
        return first_tok[:, None]
    (last_tok, *_), toks = jax.lax.scan(
        step, (first_tok, *cache, scan_rng), jnp.arange(max_new_tokens - 1)
    )
    return jnp.concatenate([toks.transpose(1, 0), last_tok[:, None]], axis=1)


def generate_beam(
    variables,
    prompt: jax.Array,
    max_new_tokens: int,
    cfg: dict,
    beam_size: int = 4,
    eos_id: int = 1,
    length_penalty_alpha: float = 0.0,
    cache_dtype=None,
    stacked_params: dict | None = None,
):
    """Beam-search continuation of ``prompt``: returns
    ``(sequences [B, beam, max_new_tokens], scores [B, beam])`` best-first.

    Built on the generic :func:`paddle_tpu.ops.control_flow.beam_search`
    (the reference's beam_search/beam_search_decode op pair — beam search is
    a first-class path there, ``operators/beam_search_op.cc``) over
    :func:`generate`'s decoder with the cache's layer axis at dim 1, because
    beam_search tiles dim 0 of every carry leaf: the prompt minus its last
    token is prefilled into the cache, each row's last prompt token seeds
    its beams, and every scan step attends against cache[0..t].
    ``cfg['scan_layers']``, ``stacked_params`` and ``cache_dtype`` as in
    :func:`generate` (deep-model beam decode then pays O(1) compile cost,
    VERDICT r4 #6).
    """
    from paddle_tpu.ops import control_flow as ocf

    B, Tp = prompt.shape
    enforce(Tp >= 1, "generate_beam needs a non-empty prompt")
    enforce(
        not cfg.get("moe_experts"),
        "generate_beam: MoE FFNs are not supported in the cached decoders "
        "yet — use a dense-FFN config",
    )
    ops, cache, run = _static_decoder(
        variables, cfg, B, Tp + max_new_tokens, 1, cache_dtype, stacked_params)
    # --- prefill positions [0, Tp-1): full causal pass over the prompt head
    Thead = Tp - 1
    if Thead > 0:
        run(prompt[:, :Thead], 0, prefill=True)

    # --- beam decode: carry leaves are [B, ...] (beam_search tiles dim 0)
    def step_fn(carry, tokens):
        cache[:] = carry["k"], carry["v"]
        y = run(tokens[:, None], carry["t"][0])
        logp = jax.nn.log_softmax(ops.logits_of(y[:, -1]).astype(jnp.float32), -1)
        return {"k": cache[0], "v": cache[1], "t": carry["t"] + 1}, logp

    return ocf.beam_search(
        step_fn,
        {"k": cache[0], "v": cache[1], "t": jnp.full((B,), Thead, jnp.int32)},
        batch_size=B,
        beam_size=beam_size,
        vocab_size=cfg["vocab"],
        max_len=max_new_tokens,
        bos_id=prompt[:, -1],
        eos_id=eos_id,
        length_penalty_alpha=length_penalty_alpha,
    )


# ---- paged decode (serving.kv_cache / serving.decode) ---------------------
#
# The paged variant of generate()'s cache read/write: K/V live in fixed-size
# pages and each sequence maps logical positions to physical pages through an
# int32 page-table row. Every array shape below is a function of static
# config (slot count, table width, page size) — never of which requests are
# in flight — so the serving programs compile once and continuous batching
# (admit/evict between steps) never pays XLA again. How a page array is
# indexed is spelled three times: ``paged_cache_shape``, ``_paged_attend``
# (the page write and the gather) and the copies of
# ``ops/pallas/paged_attention.py``, which a decode step on a TPU (and the
# latent family's chunk) attends through in the gather's place.
# Its form, ``[planes, num_pages, page_size, H_kv * dh]`` (planes being the
# layers or, where a stack runs several passes, passes x layers:
# ``models/looped_lm.py`` hands ``_paged_attend`` the plane ``r * L + i``
# where this file hands it the layer ``i``), is the one the page write takes: a row of 128 lanes or a multiple of them is held by the chip
# as spelled, so no program converts the array on entry or on exit (with
# heads an axis of their own and ``dh`` 64 it did, eight times an iteration).


def _paged_enforce(cfg, temperature, rng):
    enforce(
        not cfg.get("scan_layers"),
        "paged decode: scan_layers is not supported in the paged path yet "
        "(v1 scope: the layer loop is unrolled; use generate() for "
        "scan-layers decode)",
    )
    enforce(
        not cfg.get("moe_experts"),
        "paged decode: transformer_lm's capacity-dropping MoE FFN has no "
        "paged path; an expert layer is served by the latent_moe_lm family "
        "(ops/moe.py: a top-k router that drops nothing)",
    )
    enforce(
        temperature == 0.0 or rng is not None,
        "paged decode: sampling (temperature > 0) needs an explicit rng key",
    )


def kv_heads(cfg: dict) -> int:
    """``H_kv``: the heads a cached K or V row holds, fewer than the query
    heads under grouped-query attention."""
    return cfg.get("num_kv_heads") or cfg["num_heads"]


def paged_cache_shape(cfg: dict, num_pages: int, page_size: int):
    """Shape of ``k_pages``/``v_pages`` for ``cfg``:
    ``[L, num_pages, page_size, H_kv * dh]``: a position's K (or V) is one
    contiguous row of all its heads, the form the page write takes."""
    dh = cfg["d_model"] // cfg["num_heads"]
    return (cfg["n_layers"], num_pages, page_size, kv_heads(cfg) * dh)


def sample_logits(logits, key, temperature, top_k, top_p):
    """Greedy argmax at temperature 0, else temperature / top-k / top-p
    sampling with ``key``: the one sampler of every decoder."""
    if temperature == 0.0:
        return jnp.argmax(logits, -1).astype(jnp.int32)
    logits = logits / temperature
    if top_k is not None:
        kth = jax.lax.top_k(logits, top_k)[0][..., -1:]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    if top_p is not None:
        # nucleus: keep the smallest prefix of sorted probs with
        # cumulative mass >= top_p (the top token always survives)
        sorted_logits = jnp.sort(logits, axis=-1)[..., ::-1]
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        keep_sorted = cum - probs < top_p
        cutoff = jnp.min(
            jnp.where(keep_sorted, sorted_logits, jnp.inf), axis=-1,
            keepdims=True,
        )
        logits = jnp.where(logits < cutoff, -jnp.inf, logits)
    return jax.random.categorical(key, logits).astype(jnp.int32)


def _kv_core(q, gather, live):
    """Grouped-query softmax attention over the K and V page arrays: a
    gathered row is the position's heads side by side."""
    B, H, dh = q.shape[0], q.shape[1], q.shape[-1]

    def context(j):  # [B, H_kv, t_eff, dh]
        return jnp.moveaxis(gather(j).reshape(B, live.shape[-1], -1, dh), 1, 2)

    return _attend_cached(
        q.reshape(B, H, -1, dh), context(0), context(1), live).reshape(q.shape)


def step_attends_in_kernel(pages, page_size: int, head_dim: int, window) -> bool:
    """Whether a program over page arrays shaped as ``pages`` attends through
    ``ops/pallas/paged_attention.py``'s kernels and not the gather (a decode
    step over K and V pages; a step and a chunk over the latent family's one
    array, whose row is one head): on a TPU (``ops/moe.py``'s rule for
    ``moe_gmm``), with no sliding window, pages that lie in whole tiles, and
    no mesh of several devices around the trace (a Mosaic kernel cannot be
    partitioned automatically; the engine traces a replica group's programs
    under the group's mesh)."""
    from paddle_tpu.ops.pallas.paged_attention import step_fits

    mesh = jax.sharding.get_abstract_mesh()
    return (jax.default_backend() == "tpu" and window is None
            and step_fits(pages.shape, pages.dtype, page_size, head_dim)
            and all(n == 1 for n in mesh.shape.values()))


def _heads_last(new):  # [B, n, Q, dh] or [S, n, dh]: a position's heads side by side
    return jnp.moveaxis(new, 1, -2)


def _kv_step_in_kernel(q, pages, plane, page_tables, pos):
    from paddle_tpu.ops.pallas.paged_attention import paged_attend_step

    with jax.named_scope("paged_attend"):
        ctx = paged_attend_step(q.reshape(pos.shape[0], -1, q.shape[-1]), *pages, plane,
                                page_tables, pos)
    return ctx.reshape(q.shape)


# the programs of the K and V families that have a kernel form (ROADMAP A5
# has the chunk and the verify block)
KV_KERNELS = {"step": _kv_step_in_kernel}


def kv_attends_in_kernel(cfg: dict, pages, page_size: int) -> tuple:
    """``ServingPrograms.attends_in_kernel`` of the families whose page
    arrays are a K and a V of whole heads (``kv_heads(cfg)`` of them)."""
    fits = step_attends_in_kernel(pages, page_size, pages.shape[3] // kv_heads(cfg),
                                  cfg.get("attention_window"))
    return tuple(KV_KERNELS) if fits else ()


def _paged_attend(pages: list, page_tables, pos, page_size: int, window,
                  core=_kv_core, to_row=_heads_last, kernels=None):
    """``attend(i, q, *new)`` of queries at absolute positions ``pos``: [C]
    of the one sequence whose table ``page_tables`` [P] is (a chunk), or [S]
    (a step) or [S, Q] (a verify block) with a table row a slot [S, P]. It
    writes the queries' ``new`` rows, one per array of ``pages`` (K and V,
    the pre-rotated K exactly as generate() stores it; or the one latent row
    of ``models/latent_moe_lm.py``), into their pages, gathers each
    sequence's whole logical context [0, P * page_size) back through its
    table (the rows just written included) and attends under the live mask.
    ``pages`` is the list of page arrays, each [L, page, offset, row]; it is
    read and rebound layer by layer. ``to_row`` brings a ``new`` into the
    order of its rows (it is then reshaped to ``pos.shape + (row,)``);
    ``core(q, gather, live)`` is the attention itself, ``gather(j)`` the
    context of array ``j`` as the table gathers it, ``page_tables.shape +
    (page_size, row)``, and ``live`` the mask [B, 1, 1, Q, t_eff].

    The gather materializes each sequence's [T_eff, row] context per layer,
    the straightforward XLA lowering, whatever is live. ``kernels`` are the
    core's forms that read the live pages where they lie instead, by
    program (``"step"``, ``"chunk"``): ``kernels[program](q, pages, i,
    page_tables, pos)``. ``_kv_core``'s is ``KV_KERNELS``, the decode step
    through ``paged_attend_step``; the latent family hands the step's and the
    chunk's beside its core. A program takes its kernel under
    :func:`step_attends_in_kernel`'s rule, a one-array cache's row counting
    as one head; a program whose core has none (a K and V chunk, a verify
    block: ROADMAP A5) and every program lowered for a CPU keep the
    gather."""
    P = page_tables.shape[-1]
    B, t_eff = page_tables.size // P, P * page_size
    page, off = pos // page_size, pos % page_size
    if core is _kv_core:
        kernels = KV_KERNELS
    program = "chunk" if page_tables.ndim == 1 else "step" if pos.ndim == 1 else "verify"
    kernel = (kernels or {}).get(program)
    if page_tables.ndim == 1:  # no slot axis to index: the chunk's program stays as it compiled
        phys = page_tables[page]
    else:
        slot = jnp.arange(B).reshape((B,) + (1,) * (pos.ndim - 1))
        phys = page_tables[slot, page]
    live = _live_mask(pos, t_eff, window).reshape(B, 1, 1, -1, t_eff)

    def attend(i, q, *new):  # q [B, n, Q, dh], or [S, n, dh]
        with jax.named_scope("page_write"):
            for j, rows in enumerate(new):
                row = to_row(rows).reshape(pos.shape + (-1,))
                pages[j] = pages[j].at[i, phys, off].set(row.astype(pages[j].dtype))

        if kernel is not None and step_attends_in_kernel(
                pages[0], page_size,
                pages[0].shape[-1] if len(pages) == 1 else q.shape[-1], window):
            return kernel(q, pages, i, page_tables, pos)

        def gather(j):
            # layer and page are one index into [L * num_pages, offset,
            # row], a bitcast of the array: no layer's slice is
            # materialised first
            pg = pages[j]
            return jnp.take(pg.reshape((-1,) + pg.shape[2:]),
                            i * pg.shape[1] + page_tables, axis=0, mode="clip")

        return core(q, gather, live)

    return attend


def _paged_hidden(params, tokens, rows, page_tables, pos, k_pages, v_pages, *,
                  cfg, page_size):
    """``tokens`` (``rows(table)`` picks their positions' rows of a position
    table) through every layer against the paged cache, ``page_tables`` and
    ``pos`` as :func:`_paged_attend` takes them. Returns
    ``(ops, x [*tokens.shape, D], k_pages, v_pages)``."""
    ops = _decode_ops(_params_of(params).__getitem__, cfg)
    n_pos = max(cfg["max_len"], page_tables.shape[-1] * page_size)
    x, rotate = _embed(ops, tokens, rows, n_pos), _rotation(ops, rows, n_pos)
    pages = [k_pages, v_pages]
    attend = _paged_attend(pages, page_tables, pos, page_size, cfg.get("attention_window"))
    for i in range(cfg["n_layers"]):
        x = decode_block(ops, x, i, rotate, attend)
    return ops, x, *pages


def paged_prefill_chunk(
    params,
    tokens: jax.Array,
    pos0: jax.Array,
    last_index: jax.Array,
    page_table: jax.Array,
    k_pages: jax.Array,
    v_pages: jax.Array,
    rng: jax.Array | None = None,
    *,
    cfg: dict,
    page_size: int,
    temperature: float = 0.0,
    top_k: int | None = None,
    top_p: float | None = None,
):
    """Prefill ONE sequence's chunk into its pages: ``tokens`` [C] int32 at
    absolute positions ``[pos0, pos0+C)``, mapped through ``page_table``
    [P] int32. Returns ``(next_token, k_pages, v_pages)`` where
    ``next_token`` (scalar int32) is sampled from the logits at chunk
    index ``last_index`` — meaningful only on the prompt's final chunk
    (the first generated token); earlier chunks ignore it.

    Long prompts run as a sequence of fixed-``C`` chunks (the final one
    padded up), so prompt length never changes the compiled program and a
    long prefill never monopolizes the decode loop — the engine interleaves
    one chunk per iteration. Queries at padded positions (>= the prompt
    end) write K/V that decode overwrites position-by-position before ever
    attending to them, and their own outputs are discarded.
    """
    _paged_enforce(cfg, temperature, rng)
    (C,) = tokens.shape
    ops, x, k_pages, v_pages = _paged_hidden(
        params, tokens[None],
        lambda table: jax.lax.dynamic_slice_in_dim(table, pos0, C, axis=0)[None],
        page_table, pos0 + jnp.arange(C, dtype=jnp.int32), k_pages, v_pages,
        cfg=cfg, page_size=page_size)
    with jax.named_scope("head"):
        x_last = jax.lax.dynamic_index_in_dim(x[0], last_index, 0, keepdims=False)
        logits = ops.logits_of(x_last)
    with jax.named_scope("sampling"):
        tok = sample_logits(logits, rng, temperature, top_k, top_p)
    return tok, k_pages, v_pages


def paged_decode_step(
    params,
    tokens: jax.Array,
    positions: jax.Array,
    page_tables: jax.Array,
    k_pages: jax.Array,
    v_pages: jax.Array,
    rng: jax.Array | None = None,
    *,
    cfg: dict,
    page_size: int,
    temperature: float = 0.0,
    top_k: int | None = None,
    top_p: float | None = None,
):
    """One decode iteration for ``S`` independent sequences against the
    paged cache: embed ``tokens`` [S] at per-slot absolute ``positions``
    [S], write each token's K/V into its slot's pages, attend over each
    slot's gathered context, and sample the next token. Returns
    ``(next_tokens [S], k_pages, v_pages)``.

    Shapes depend only on (S, table width, page size, model config) — the
    continuous-batching contract: slots change occupants between calls
    without recompiling. Inactive slots point at the scratch page; their
    writes and outputs are garbage the engine ignores.
    """
    _paged_enforce(cfg, temperature, rng)
    ops, x, k_pages, v_pages = _paged_hidden(
        params, tokens, lambda table: table[positions], page_tables, positions,
        k_pages, v_pages, cfg=cfg, page_size=page_size)
    with jax.named_scope("head"):
        logits = ops.logits_of(x)
    with jax.named_scope("sampling"):
        nxt = sample_logits(logits, rng, temperature, top_k, top_p)
    return nxt, k_pages, v_pages


def paged_verify_step(
    params,
    tokens: jax.Array,
    positions: jax.Array,
    page_tables: jax.Array,
    k_pages: jax.Array,
    v_pages: jax.Array,
    *,
    cfg: dict,
    page_size: int,
):
    """One speculative verify iteration for ``S`` sequences: score a block
    of ``K+1`` tokens per slot against the paged cache in a single jitted
    call. ``tokens`` [S, K+1] holds slot ``s``'s last sampled token followed
    by its ``K`` draft proposals; they occupy absolute positions
    ``positions[s] .. positions[s]+K``. All K+1 K/V rows are written into
    the slot's pages, the block attends causally over the gathered context
    (token ``j`` sees every earlier position plus drafts ``< j`` written
    this same call, exactly like a prefill chunk), and the return value
    ``out`` [S, K+1] is the greedy argmax after each position — i.e.
    ``out[s, j]`` is what sequential decode would have sampled after
    consuming ``tokens[s, :j+1]``. The engine accepts the longest prefix
    with ``draft[j] == out[s, j-1]``, which makes greedy speculative decode
    token-exact by construction.

    Greedy only: acceptance compares argmaxes, so sampling temperature
    would break exactness — the engine enforces ``temperature == 0``.
    Shapes depend only on (S, K, table width, page size, model config), so
    this compiles once ever, same as :func:`paged_decode_step`. Rejected
    draft positions need no device-side rollback: their K/V rows sit past
    the accepted frontier, masked (``t > q_pos``) until the next block
    overwrites them.
    """
    _paged_enforce(cfg, 0.0, None)
    pos = positions[:, None] + jnp.arange(tokens.shape[1], dtype=jnp.int32)  # [S, K+1]
    ops, x, k_pages, v_pages = _paged_hidden(
        params, tokens, lambda table: table[pos], page_tables, pos,
        k_pages, v_pages, cfg=cfg, page_size=page_size)
    with jax.named_scope("head"):
        logits = ops.logits_of(x)
    with jax.named_scope("sampling"):
        out = sample_logits(logits, None, 0.0, None, None)  # [S, K+1]
    return out, k_pages, v_pages


BASE_CFG = dict(
    vocab=32000,
    d_model=512,
    d_inner=2048,
    num_heads=8,
    num_kv_heads=None,  # < num_heads -> grouped-query attention
    pos_encoding="sinusoid",  # or "rope" (rotary, applied at attention)
    ffn_activation="relu",  # or "swiglu"
    attention_window=None,  # int -> sliding-window attention (O(T*W))
    n_layers=6,
    max_len=8192,
    attn_dropout=0.0,
    relu_dropout=0.0,
    residual_dropout=0.0,
    remat=False,
    # run the layer stack as one lax.scan over stacked params: compile time
    # O(1) in n_layers (see _scan_lm_blocks); dropout stream differs from
    # the unrolled loop, math is otherwise identical
    scan_layers=False,
    # mixture-of-experts FFN (parallel/moe.py): 0 = dense. Expert weights
    # shard over the 'expert' mesh axis; the router aux (load-balance) loss
    # joins the training loss with moe_aux_weight
    moe_experts=0,
    moe_router="top1",  # or "top2" (GShard pair dispatch)
    moe_capacity_factor=1.25,
    moe_aux_weight=0.01,
)


def paged_cache_specs(cfg: dict, *, num_pages: int, page_size: int, dtype, **_):
    """The two page arrays (K and V) the engine allocates for ``cfg``."""
    shape = paged_cache_shape(cfg, num_pages, page_size)
    return (jax.ShapeDtypeStruct(shape, dtype),) * 2


def serving_programs():
    from paddle_tpu.models import ServingPrograms

    return ServingPrograms(
        cache="pages", cache_args=("k_pages", "v_pages"),
        cache_specs=paged_cache_specs, prefill_chunk=paged_prefill_chunk,
        decode_step=paged_decode_step, verify_step=paged_verify_step,
        mechanism="softmax attention over a paged KV cache", kv_heads=kv_heads,
        attends_in_kernel=kv_attends_in_kernel)


def get_model(
    seq_len: int = 1024, learning_rate: float = 1e-3, ring_mesh=None,
    ulysses_mesh=None, **overrides
) -> ModelSpec:
    """``ring_mesh``: a Mesh with a ``seq`` axis → attention runs as ring
    attention over it (sequence-parallel exact attention; batch tokens must
    be fed sharded [data, seq]). ``ulysses_mesh``: same contract but via
    all-to-all head resharding (``ops/ulysses.py``) — pick ring for
    T >> heads, ulysses for heads >= seq-axis size."""
    cfg = dict(BASE_CFG)
    cfg.update({k: v for k, v in overrides.items() if k in cfg})
    cfg["max_len"] = max(cfg["max_len"], seq_len)
    if ring_mesh is not None:
        cfg["ring_mesh"] = ring_mesh
    if ulysses_mesh is not None:
        cfg["ulysses_mesh"] = ulysses_mesh
    if overrides.get("pipe_mesh") is not None:
        cfg["pipe_mesh"] = overrides["pipe_mesh"]
        cfg["pipe_n_micro"] = overrides.get("pipe_n_micro")

    model = pt.build(functools.partial(lm_forward, cfg=cfg), name="transformer_lm")

    def synth_batch(batch_size: int, rng: np.random.RandomState):
        ids = rng.randint(1, cfg["vocab"], size=(batch_size, seq_len)).astype(np.int32)
        labels = rng.randint(1, cfg["vocab"], size=(batch_size, seq_len)).astype(np.int32)
        return ids, labels

    return ModelSpec(
        name="transformer_lm",
        model=model,
        synth_batch=synth_batch,
        optimizer=lambda: pt.optimizer.Adam(learning_rate=learning_rate),
        unit="tokens/sec",
        examples_per_row=seq_len,
        extra={"cfg": cfg, "seq_len": seq_len},
    )
