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
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

import paddle_tpu as pt
from paddle_tpu import layers
from paddle_tpu.framework import name_scope
from paddle_tpu.models import ModelSpec
from paddle_tpu.models.transformer import (
    _post_process,
    _proj,
    multi_head_attention,
    positionwise_ffn,
    prepare_embedding,
)

__all__ = ["get_model", "lm_forward", "generate", "generate_beam",
           "stack_decode_params", "BASE_CFG",
           "paged_cache_shape", "paged_prefill_chunk", "paged_decode_step",
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
    from paddle_tpu.ops.attention import apply_rope, rope_tables, scaled_dot_product_attention

    def core(qh, kh, vh, kv_len=None):
        cos, sin = rope_tables(qh.shape[-1], qh.shape[-2])
        return scaled_dot_product_attention(
            apply_rope(qh, cos, sin), apply_rope(kh, cos, sin), vh, causal=True,
            window=cfg.get("attention_window"), kv_len=kv_len,
        )

    return core


def _decode_ffn_fn(proj, swiglu: bool):
    """FFN for the cached decoders, pinned to ``positionwise_ffn``:
    relu(fc1) or fc1 * silu(gate). One copy shared by generate and
    generate_beam so train/decode FFN parity has a single edit point."""
    def ffn(x, i):
        if swiglu:
            h = proj(x, f"layer_{i}/ffn/fc1") * jax.nn.silu(proj(x, f"layer_{i}/ffn/gate"))
        else:
            h = jax.nn.relu(proj(x, f"layer_{i}/ffn/fc1"))
        return proj(h, f"layer_{i}/ffn/fc2")

    return ffn


def _live_mask(t_max: int, t, window):
    """[t_max] bool mask of cache positions a token at position ``t`` may
    attend: <= t, and within the last ``window`` positions when sliding."""
    live = jnp.arange(t_max) <= t
    if window is not None:
        live &= jnp.arange(t_max) > t - window
    return live


def _with_rope(core):
    """Wrap a sequence-parallel attention core with RoPE: the rotation is
    per-position (applied on the GLOBAL [B, H, T, d] arrays before the core
    shards them), so rope composes exactly with ring/ulysses."""
    from paddle_tpu.ops.attention import apply_rope, rope_tables

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


def stack_decode_params(variables_or_params, cfg: dict) -> dict:
    """Stack the per-layer parameter arrays for ``scan_layers`` decode:
    {suffix: [L, ...]}. Call ONCE outside the jitted decode (or let jit
    close over the result) so the stack is not re-copied per call; pass to
    :func:`generate` as ``stacked_params``."""
    params = (variables_or_params.params
              if hasattr(variables_or_params, "params") else variables_or_params)
    return pt.framework.stack_layer_params(
        params, cfg["n_layers"], lambda i: f"layer_{i}"
    )


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

    Implemented directly over the trained params dict (names as created by
    :func:`lm_forward`) so the decode loop is a plain jittable function —
    greedy at ``temperature=0``, else softmax sampling with ``rng``
    (required then). Deliberately NOT built on ``lm_block``: a scan-stepped
    static cache can't use ``multi_head_attention``'s shape-growing
    concatenate cache, and re-entering ``name_scope``s inside a scan body
    would re-uniquify parameter names. The decode math is pinned to
    ``lm_forward`` by ``test_transformer_lm_generate_matches_naive_decode``
    — change one, and that exact-match test catches the drift.

    ``cache_dtype`` (default f32): the k/v cache dtype. ``jnp.bfloat16``
    halves decode HBM traffic — the decode-throughput lever on TPU, where
    each step streams the whole cache — at bf16 rounding of cached keys/
    values (scores still accumulate f32; confident predictions are
    unaffected, see the memorized-decode test).
    """
    from paddle_tpu.core.enforce import enforce
    from paddle_tpu.models.transformer import sinusoid_position_encoding

    params = variables.params if hasattr(variables, "params") else variables
    B, Tp = prompt.shape
    T_max = Tp + max_new_tokens
    D, H, L = cfg["d_model"], cfg["num_heads"], cfg["n_layers"]
    dh = D // H
    H_kv = cfg.get("num_kv_heads") or H  # GQA: cache holds H_kv heads
    G = H // H_kv
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
    rope = cfg.get("pos_encoding", "sinusoid") == "rope"
    swiglu = cfg.get("ffn_activation", "relu") == "swiglu"
    window = cfg.get("attention_window")
    pe = sinusoid_position_encoding(max(cfg["max_len"], T_max), D)
    if rope:
        from paddle_tpu.ops.attention import apply_rope, rope_tables

        rope_cos, rope_sin = rope_tables(dh, max(cfg["max_len"], T_max))
    scale = 1.0 / np.sqrt(dh)

    # scan-over-layers decode (cfg['scan_layers']): layer params stack to
    # [L, ...] by suffix and the per-token layer loop runs as a lax.scan;
    # inside the scan body the block's name-based lookups resolve through
    # ``scan_view`` via the reserved 'layer_SCAN/' prefix (the decode-side
    # analogue of framework.scan_layer_stack — compile cost O(1) in depth)
    scan_layers = bool(cfg.get("scan_layers"))
    scan_view: dict = {}
    if scan_layers:
        # prefer a caller-prestacked tree (stack_decode_params, built once
        # OUTSIDE jit / closed over by it) — stacking here would copy the
        # full parameter set on every jitted decode call
        stacked = (stacked_params if stacked_params is not None
                   else stack_decode_params(params, cfg))

    def p(name):
        if name.startswith("layer_SCAN/"):
            return scan_view[name[len("layer_SCAN/"):]]
        return params[name]

    def ln(x, pfx):
        mu = jnp.mean(x, -1, keepdims=True)
        var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
        return (x - mu) * jax.lax.rsqrt(var + 1e-5) * p(f"{pfx}/scale") + p(f"{pfx}/bias")

    def proj(x, pfx, bias=True):
        out = x @ p(f"{pfx}/w")
        return out + p(f"{pfx}/b") if bias else out

    ffn = _decode_ffn_fn(proj, swiglu)

    def heads(x, n=None):  # [B, T, n*dh] -> [B, n, T, dh]
        n = n or H
        return x.reshape(x.shape[0], x.shape[1], n, dh).transpose(0, 2, 1, 3)

    def grouped(q):  # [B, H, T, dh] -> [B, H_kv, G, T, dh]
        return q.reshape(q.shape[0], H_kv, G, q.shape[2], dh)

    def ungrouped(o):  # [B, H_kv, G, T, dh] -> [B, H, T, dh]
        return o.reshape(o.shape[0], H, o.shape[3], dh)

    def embed(ids, pos0):
        e = jnp.take(p("emb/embedding/word_emb"), ids, axis=0) * (D ** 0.5)
        if rope:  # position enters at the attention rotation instead
            return e
        t = ids.shape[1]
        return e + jax.lax.dynamic_slice_in_dim(pe, pos0, t, axis=0)

    def rotate(x, pos0):
        """RoPE at absolute positions [pos0, pos0+T): cached K is stored
        PRE-rotated (rotation depends only on the key's own position, and
        scores depend only on relative offsets)."""
        t = x.shape[2]
        cos = jax.lax.dynamic_slice_in_dim(rope_cos, pos0, t, axis=0)
        sin = jax.lax.dynamic_slice_in_dim(rope_sin, pos0, t, axis=0)
        return apply_rope(x, cos, sin)

    def block(x, i, attend, pos0=0):
        pfx = f"layer_{i}/self_attn"
        q = heads(proj(x, f"{pfx}/q"))
        k = heads(proj(x, f"{pfx}/k"), H_kv)
        v = heads(proj(x, f"{pfx}/v"), H_kv)
        if rope:
            q = rotate(q, pos0)
            k = rotate(k, pos0)
        ctx = attend(q, k, v, i)  # [B, H, Tq, dh]
        ctx = ctx.transpose(0, 2, 1, 3).reshape(x.shape[0], x.shape[1], D)
        x = ln(x + proj(ctx, f"{pfx}/out"), f"layer_{i}/layer_norm")
        return ln(x + ffn(x, i), f"layer_{i}/layer_norm_1")

    def logits_of(x_last):  # [B, D] -> [B, vocab]
        return ln(x_last, "layer_norm") @ p("project/logits/w")

    def sample(logits, key):
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
                jnp.where(keep_sorted, sorted_logits, jnp.inf), axis=-1, keepdims=True
            )
            logits = jnp.where(logits < cutoff, -jnp.inf, logits)
        return jax.random.categorical(key, logits).astype(jnp.int32)

    # ---- prefill: full causal pass over the prompt fills caches [0, Tp)
    cdt = cache_dtype or jnp.float32
    kc0 = jnp.zeros((L, B, H_kv, T_max, dh), cdt)
    vc0 = jnp.zeros((L, B, H_kv, T_max, dh), cdt)
    caches = {"k": kc0, "v": vc0}

    # sdpa routes long prompts through the flash kernel when the flag is
    # on (no [Tp, Tp] materialization) and composes the identical
    # causal+window einsum math otherwise — same path as the training
    # forward, so decode-vs-forward stays exact
    from paddle_tpu.ops.attention import scaled_dot_product_attention

    def run_layer_scan(x0, kc, vc, pos0, make_attend):
        """The shared layer-scan body for scan_layers prefill AND decode:
        repopulate the scan_view overlay from the stacked slice, run the
        block with an attend built for this layer index, carry caches."""
        def body(carry, sl):
            y, kc, vc = carry
            scan_view.clear()
            scan_view.update(sl["p"])
            li = sl["i"]

            def attend(q, k, v, _i):
                nonlocal kc, vc
                ctx, kc, vc = make_attend(q, k, v, li, kc, vc)
                return ctx

            y = block(y, "SCAN", attend, pos0=pos0)
            return (y, kc, vc), None

        return jax.lax.scan(
            body, (x0, kc, vc), {"p": stacked, "i": jnp.arange(L)}
        )[0]

    if scan_layers:
        def prefill_write(q, k, v, li, kc, vc):
            kc = kc.at[li, :, :, :Tp].set(k.astype(cdt))
            vc = vc.at[li, :, :, :Tp].set(v.astype(cdt))
            ctx = scaled_dot_product_attention(
                q, k, v, causal=True, window=window
            )
            return ctx, kc, vc

        x, kc_f, vc_f = run_layer_scan(
            embed(prompt, 0), kc0, vc0, 0, prefill_write
        )
        caches = {"k": kc_f, "v": vc_f}
    else:
        def prefill_attend(q, k, v, i):
            caches["k"] = caches["k"].at[i, :, :, :Tp].set(k.astype(cdt))
            caches["v"] = caches["v"].at[i, :, :, :Tp].set(v.astype(cdt))
            return scaled_dot_product_attention(
                q, k, v, causal=True, window=window
            )

        x = embed(prompt, 0)
        for i in range(L):
            x = block(x, i, prefill_attend, pos0=0)
    first_key, scan_rng = (
        jax.random.split(rng) if rng is not None else (None, None)
    )
    first_tok = sample(logits_of(x[:, -1]), first_key)

    # ---- decode: one token per scan step against the cache
    def step(carry, s):
        tok, kc, vc, key = carry
        t = Tp + s  # position of this token
        xt = embed(tok[:, None], t)  # [B, 1, D] — pos0 is traced; ok for slice

        def cached_attend(q, k, v, li, kc, vc):
            """One token's attention against layer ``li``'s cache rows
            (li may be traced under the layer scan); returns the updated
            caches alongside the context."""
            kc = jax.lax.dynamic_update_slice(kc, k[None].astype(cdt), (li, 0, 0, t, 0))
            vc = jax.lax.dynamic_update_slice(vc, v[None].astype(cdt), (li, 0, 0, t, 0))
            kci = jax.lax.dynamic_index_in_dim(kc, li, 0, keepdims=False)
            vci = jax.lax.dynamic_index_in_dim(vc, li, 0, keepdims=False)
            s_ = jnp.einsum("bkgqd,bktd->bkgqt", grouped(q), kci) * scale
            live = _live_mask(T_max, t, window)
            s_ = jnp.where(live[None, None, None, None, :], s_, -1e9)
            ctx = ungrouped(
                jnp.einsum("bkgqt,bktd->bkgqd", jax.nn.softmax(s_, -1), vci)
            )
            return ctx, kc, vc

        if scan_layers:
            y, kc, vc = run_layer_scan(xt, kc, vc, t, cached_attend)
        else:
            def attend_i(q, k, v, i):
                nonlocal kc, vc
                ctx, kc, vc = cached_attend(q, k, v, i, kc, vc)
                return ctx

            y = xt
            for i in range(L):
                y = block(y, i, attend_i, pos0=t)
        if key is not None:
            key, sub = jax.random.split(key)
        else:
            sub = None
        nxt = sample(logits_of(y[:, -1]), sub)
        return (nxt, kc, vc, key), tok

    if max_new_tokens == 1:
        return first_tok[:, None]
    carry = (first_tok, caches["k"], caches["v"], scan_rng)
    (last_tok, _, _, _), toks = jax.lax.scan(
        step, carry, jnp.arange(max_new_tokens - 1)
    )
    return jnp.concatenate([toks.transpose(1, 0), last_tok[:, None]], axis=1)


# ---- paged decode (serving.kv_cache / serving.decode) ---------------------
#
# The paged variant of generate()'s cache read/write: K/V live in fixed-size
# pages ([L, num_pages, H_kv, page_size, dh]) and each sequence maps logical
# positions to physical pages through an int32 page-table row. Every array
# shape below is a function of static config (slot count, table width, page
# size) — never of which requests are in flight — so the serving decode step
# compiles once and continuous batching (admit/evict between steps) never
# pays XLA again. Same parameter names and attention math as generate();
# the exactness test pins the two against each other.


def _paged_enforce(cfg, temperature, rng):
    from paddle_tpu.core.enforce import enforce

    enforce(
        not cfg.get("scan_layers"),
        "paged decode: scan_layers is not supported in the paged path yet "
        "(v1 scope: the layer loop is unrolled; use generate() for "
        "scan-layers decode)",
    )
    enforce(
        not cfg.get("moe_experts"),
        "paged decode: MoE FFNs are not supported in the cached decoders — "
        "use a dense-FFN config",
    )
    enforce(
        temperature == 0.0 or rng is not None,
        "paged decode: sampling (temperature > 0) needs an explicit rng key",
    )


def paged_cache_shape(cfg: dict, num_pages: int, page_size: int):
    """Shape of ``k_pages``/``v_pages`` for ``cfg``:
    ``[L, num_pages, H_kv, page_size, dh]``."""
    H = cfg["num_heads"]
    H_kv = cfg.get("num_kv_heads") or H
    dh = cfg["d_model"] // H
    return (cfg["n_layers"], num_pages, H_kv, page_size, dh)


def sample_logits(logits, key, temperature, top_k, top_p):
    """Greedy argmax at temperature 0, else temperature / top-k / top-p
    sampling with ``key``: the one sampler of every serving program."""
    if temperature == 0.0:
        return jnp.argmax(logits, -1).astype(jnp.int32)
    logits = logits / temperature
    if top_k is not None:
        kth = jax.lax.top_k(logits, top_k)[0][..., -1:]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    if top_p is not None:
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


def _paged_ops(params, cfg):
    """The p/ln/proj/ffn/logits/sample closures shared by the paged prefill
    and decode-step entry points — the same math as :func:`generate`'s
    inline copies (parameter names as created by :func:`lm_forward`)."""
    D, H = cfg["d_model"], cfg["num_heads"]
    dh = D // H
    swiglu = cfg.get("ffn_activation", "relu") == "swiglu"

    def p(name):
        return params[name]

    def ln(x, pfx):
        mu = jnp.mean(x, -1, keepdims=True)
        var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
        return (x - mu) * jax.lax.rsqrt(var + 1e-5) * p(f"{pfx}/scale") + p(f"{pfx}/bias")

    def proj(x, pfx, bias=True):
        out = x @ p(f"{pfx}/w")
        return out + p(f"{pfx}/b") if bias else out

    ffn = _decode_ffn_fn(proj, swiglu)

    def logits_of(x_last):
        return ln(x_last, "layer_norm") @ p("project/logits/w")

    return p, ln, proj, ffn, logits_of, sample_logits


def _paged_live_mask(q_pos, t_eff: int, window):
    """[..., T_eff] bool: key position t visible from query position
    ``q_pos`` ([...] int32) — causal, and within the sliding window when
    configured. The gathered pages cover logical positions [0, T_eff); any
    slot beyond the sequence's written length is > q_pos and masks out."""
    t = jnp.arange(t_eff)
    live = t <= q_pos[..., None]
    if window is not None:
        live &= t > q_pos[..., None] - window
    return live


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
    from paddle_tpu.models.transformer import sinusoid_position_encoding

    params = params.params if hasattr(params, "params") else params
    _paged_enforce(cfg, temperature, rng)
    (C,) = tokens.shape
    P = page_table.shape[0]
    t_eff = P * page_size
    D, H = cfg["d_model"], cfg["num_heads"]
    dh = D // H
    H_kv = cfg.get("num_kv_heads") or H
    G = H // H_kv
    L = cfg["n_layers"]
    rope = cfg.get("pos_encoding", "sinusoid") == "rope"
    window = cfg.get("attention_window")
    scale = 1.0 / np.sqrt(dh)
    cdt = k_pages.dtype
    p, ln, proj, ffn, logits_of, sample = _paged_ops(params, cfg)

    with jax.named_scope("embed"):
        e = jnp.take(p("emb/embedding/word_emb"), tokens, axis=0) * (D ** 0.5)
        if rope:
            from paddle_tpu.ops.attention import apply_rope, rope_tables

            rope_cos, rope_sin = rope_tables(dh, max(cfg["max_len"], t_eff))
        else:
            pe = sinusoid_position_encoding(max(cfg["max_len"], t_eff), D)
            e = e + jax.lax.dynamic_slice_in_dim(pe, pos0, C, axis=0)
        x = e[None]  # [1, C, D]
    pos = pos0 + jnp.arange(C, dtype=jnp.int32)
    phys = page_table[pos // page_size]  # [C] physical page per position
    off = pos % page_size
    live = _paged_live_mask(pos, t_eff, window)  # [C, T_eff]

    def heads(y, n):  # [1, C, n*dh] -> [1, n, C, dh]
        return y.reshape(1, C, n, dh).transpose(0, 2, 1, 3)

    for i in range(L):
        pfx = f"layer_{i}/self_attn"
        with jax.named_scope("attention"):
            q = heads(proj(x, f"{pfx}/q"), H)
            k = heads(proj(x, f"{pfx}/k"), H_kv)
            v = heads(proj(x, f"{pfx}/v"), H_kv)
            if rope:
                cos = jax.lax.dynamic_slice_in_dim(rope_cos, pos0, C, axis=0)
                sin = jax.lax.dynamic_slice_in_dim(rope_sin, pos0, C, axis=0)
                q, k = apply_rope(q, cos, sin), apply_rope(k, cos, sin)
        # scatter the chunk's K/V into this sequence's pages (pre-rotated
        # K, exactly as generate() stores it)
        with jax.named_scope("page_write"):
            k_pages = k_pages.at[i, phys, :, off].set(
                k[0].transpose(1, 0, 2).astype(cdt))
            v_pages = v_pages.at[i, phys, :, off].set(
                v[0].transpose(1, 0, 2).astype(cdt))
        # gather the sequence's whole logical context back through the
        # table (includes the chunk just written) and mask by position
        with jax.named_scope("attention"):
            kl = k_pages[i][page_table].transpose(1, 0, 2, 3).reshape(
                H_kv, t_eff, dh)[None]
            vl = v_pages[i][page_table].transpose(1, 0, 2, 3).reshape(
                H_kv, t_eff, dh)[None]
            qg = q.reshape(1, H_kv, G, C, dh)
            s = jnp.einsum("bkgqd,bktd->bkgqt", qg, kl) * scale
            s = jnp.where(live[None, None, None], s, -1e9)
            ctx = jnp.einsum("bkgqt,bktd->bkgqd", jax.nn.softmax(s, -1), vl)
            ctx = ctx.reshape(1, H, C, dh).transpose(0, 2, 1, 3).reshape(1, C, D)
            x = ln(x + proj(ctx, f"{pfx}/out"), f"layer_{i}/layer_norm")
        with jax.named_scope("ffn"):
            x = ln(x + ffn(x, i), f"layer_{i}/layer_norm_1")

    with jax.named_scope("head"):
        x_last = jax.lax.dynamic_index_in_dim(x[0], last_index, 0, keepdims=False)
        logits = logits_of(x_last)
    with jax.named_scope("sampling"):
        tok = sample(logits, rng, temperature, top_k, top_p)
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

    The gather materializes each slot's ``[H_kv, T_eff, dh]`` context per
    layer — the straightforward XLA lowering. A Pallas paged-attention
    kernel that streams pages from HBM without the copy is the known TPU
    follow-up; the interface (pages + tables) is already shaped for it.
    """
    from paddle_tpu.models.transformer import sinusoid_position_encoding

    params = params.params if hasattr(params, "params") else params
    _paged_enforce(cfg, temperature, rng)
    (S,) = tokens.shape
    P = page_tables.shape[1]
    t_eff = P * page_size
    D, H = cfg["d_model"], cfg["num_heads"]
    dh = D // H
    H_kv = cfg.get("num_kv_heads") or H
    G = H // H_kv
    L = cfg["n_layers"]
    rope = cfg.get("pos_encoding", "sinusoid") == "rope"
    window = cfg.get("attention_window")
    scale = 1.0 / np.sqrt(dh)
    cdt = k_pages.dtype
    p, ln, proj, ffn, logits_of, sample = _paged_ops(params, cfg)

    with jax.named_scope("embed"):
        x = jnp.take(p("emb/embedding/word_emb"), tokens, axis=0) * (D ** 0.5)
    if rope:
        from paddle_tpu.ops.attention import rope_tables

        rope_cos, rope_sin = rope_tables(dh, max(cfg["max_len"], t_eff))
        cos, sin = rope_cos[positions], rope_sin[positions]  # [S, dh//2]

        def rot(y):  # [S, n, dh] rotated at each slot's own position
            half = dh // 2
            y1, y2 = y[..., :half], y[..., half:]
            c, s_ = cos[:, None, :], sin[:, None, :]
            yf1, yf2 = y1.astype(jnp.float32), y2.astype(jnp.float32)
            return jnp.concatenate(
                [yf1 * c - yf2 * s_, yf1 * s_ + yf2 * c], -1
            ).astype(y.dtype)
    else:
        with jax.named_scope("embed"):
            pe = sinusoid_position_encoding(max(cfg["max_len"], t_eff), D)
            x = x + pe[positions]
    phys = page_tables[jnp.arange(S), positions // page_size]  # [S]
    off = positions % page_size
    live = _paged_live_mask(positions, t_eff, window)  # [S, T_eff]

    for i in range(L):
        pfx = f"layer_{i}/self_attn"
        with jax.named_scope("attention"):
            q = proj(x, f"{pfx}/q").reshape(S, H, dh)
            k = proj(x, f"{pfx}/k").reshape(S, H_kv, dh)
            v = proj(x, f"{pfx}/v").reshape(S, H_kv, dh)
            if rope:
                q, k = rot(q), rot(k)
        with jax.named_scope("page_write"):
            k_pages = k_pages.at[i, phys, :, off].set(k.astype(cdt))
            v_pages = v_pages.at[i, phys, :, off].set(v.astype(cdt))
        with jax.named_scope("attention"):
            kl = k_pages[i][page_tables].transpose(0, 2, 1, 3, 4).reshape(
                S, H_kv, t_eff, dh)
            vl = v_pages[i][page_tables].transpose(0, 2, 1, 3, 4).reshape(
                S, H_kv, t_eff, dh)
            qg = q.reshape(S, H_kv, G, dh)
            s = jnp.einsum("skgd,sktd->skgt", qg, kl) * scale
            s = jnp.where(live[:, None, None], s, -1e9)
            ctx = jnp.einsum("skgt,sktd->skgd", jax.nn.softmax(s, -1), vl)
            ctx = ctx.reshape(S, D)
            x = ln(x + proj(ctx, f"{pfx}/out"), f"layer_{i}/layer_norm")
        with jax.named_scope("ffn"):
            x = ln(x + ffn(x, i), f"layer_{i}/layer_norm_1")

    with jax.named_scope("head"):
        logits = logits_of(x)
    with jax.named_scope("sampling"):
        nxt = sample(logits, rng, temperature, top_k, top_p)
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
    from paddle_tpu.models.transformer import sinusoid_position_encoding

    params = params.params if hasattr(params, "params") else params
    _paged_enforce(cfg, 0.0, None)
    S, K1 = tokens.shape
    P = page_tables.shape[1]
    t_eff = P * page_size
    D, H = cfg["d_model"], cfg["num_heads"]
    dh = D // H
    H_kv = cfg.get("num_kv_heads") or H
    G = H // H_kv
    L = cfg["n_layers"]
    rope = cfg.get("pos_encoding", "sinusoid") == "rope"
    window = cfg.get("attention_window")
    scale = 1.0 / np.sqrt(dh)
    cdt = k_pages.dtype
    p, ln, proj, ffn, logits_of, _ = _paged_ops(params, cfg)

    with jax.named_scope("embed"):
        x = jnp.take(p("emb/embedding/word_emb"), tokens, axis=0) * (D ** 0.5)
    pos = positions[:, None] + jnp.arange(K1, dtype=jnp.int32)  # [S, K1]
    if rope:
        from paddle_tpu.ops.attention import rope_tables

        rope_cos, rope_sin = rope_tables(dh, max(cfg["max_len"], t_eff))
        cos, sin = rope_cos[pos], rope_sin[pos]  # [S, K1, dh//2]

        def rot(y):  # [S, K1, n, dh] rotated at each token's own position
            half = dh // 2
            y1, y2 = y[..., :half], y[..., half:]
            c, s_ = cos[:, :, None, :], sin[:, :, None, :]
            yf1, yf2 = y1.astype(jnp.float32), y2.astype(jnp.float32)
            return jnp.concatenate(
                [yf1 * c - yf2 * s_, yf1 * s_ + yf2 * c], -1
            ).astype(y.dtype)
    else:
        with jax.named_scope("embed"):
            pe = sinusoid_position_encoding(max(cfg["max_len"], t_eff), D)
            x = x + pe[pos]
    phys = page_tables[jnp.arange(S)[:, None], pos // page_size]  # [S, K1]
    off = pos % page_size
    live = _paged_live_mask(pos, t_eff, window)  # [S, K1, T_eff]

    for i in range(L):
        pfx = f"layer_{i}/self_attn"
        with jax.named_scope("attention"):
            q = proj(x, f"{pfx}/q").reshape(S, K1, H, dh)
            k = proj(x, f"{pfx}/k").reshape(S, K1, H_kv, dh)
            v = proj(x, f"{pfx}/v").reshape(S, K1, H_kv, dh)
            if rope:
                q, k = rot(q), rot(k)
        with jax.named_scope("page_write"):
            k_pages = k_pages.at[i, phys, :, off].set(k.astype(cdt))
            v_pages = v_pages.at[i, phys, :, off].set(v.astype(cdt))
        with jax.named_scope("attention"):
            kl = k_pages[i][page_tables].transpose(0, 2, 1, 3, 4).reshape(
                S, H_kv, t_eff, dh)
            vl = v_pages[i][page_tables].transpose(0, 2, 1, 3, 4).reshape(
                S, H_kv, t_eff, dh)
            qg = q.transpose(0, 2, 1, 3).reshape(S, H_kv, G, K1, dh)
            s = jnp.einsum("skgqd,sktd->skgqt", qg, kl) * scale
            s = jnp.where(live[:, None, None], s, -1e9)
            ctx = jnp.einsum("skgqt,sktd->skgqd", jax.nn.softmax(s, -1), vl)
            ctx = ctx.reshape(S, H, K1, dh).transpose(0, 2, 1, 3).reshape(
                S, K1, D)
            x = ln(x + proj(ctx, f"{pfx}/out"), f"layer_{i}/layer_norm")
        with jax.named_scope("ffn"):
            x = ln(x + ffn(x, i), f"layer_{i}/layer_norm_1")

    with jax.named_scope("head"):
        logits = logits_of(x)
    with jax.named_scope("sampling"):
        out = jnp.argmax(logits, -1).astype(jnp.int32)  # [S, K1]
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
        mechanism="softmax attention over a paged KV cache")


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
    a first-class path there, ``operators/beam_search_op.cc``) over the same
    static k/v cache layout as :func:`generate`: the prompt minus its last
    token is prefilled into the cache, each row's last prompt token seeds
    its beams, and every scan step attends against cache[0..t]. Same decode
    math as ``generate`` (same param names/ops); GQA cache layout included.

    ``cfg['scan_layers']`` runs the per-token (and prefill) layer loop as a
    ``lax.scan`` over stacked params, exactly as in :func:`generate` — one
    traced layer body regardless of depth, so deep-model beam decode pays
    O(1) compile cost (VERDICT r4 #6). Beam caches keep the layer axis at
    dim 1 (beam tiling stays on dim 0); the scan indexes it dynamically.
    Pass ``stacked_params`` (from :func:`stack_decode_params`) to avoid
    re-stacking per jitted call.
    """
    from paddle_tpu.core.enforce import enforce
    from paddle_tpu.models.transformer import sinusoid_position_encoding
    from paddle_tpu.ops import control_flow as ocf

    params = variables.params if hasattr(variables, "params") else variables
    B, Tp = prompt.shape
    enforce(Tp >= 1, "generate_beam needs a non-empty prompt")
    enforce(
        not cfg.get("moe_experts"),
        "generate_beam: MoE FFNs are not supported in the cached decoders "
        "yet — use a dense-FFN config",
    )
    T_max = Tp + max_new_tokens
    D, H, L = cfg["d_model"], cfg["num_heads"], cfg["n_layers"]
    dh = D // H
    H_kv = cfg.get("num_kv_heads") or H
    G = H // H_kv
    rope = cfg.get("pos_encoding", "sinusoid") == "rope"
    swiglu = cfg.get("ffn_activation", "relu") == "swiglu"
    window = cfg.get("attention_window")
    pe = sinusoid_position_encoding(max(cfg["max_len"], T_max), D)
    if rope:
        from paddle_tpu.ops.attention import apply_rope, rope_tables

        rope_cos, rope_sin = rope_tables(dh, max(cfg["max_len"], T_max))
    scale = 1.0 / np.sqrt(dh)

    scan_layers = bool(cfg.get("scan_layers"))
    scan_view: dict = {}
    if scan_layers:
        stacked = (stacked_params if stacked_params is not None
                   else stack_decode_params(params, cfg))

    def p(name):
        if name.startswith("layer_SCAN/"):
            return scan_view[name[len("layer_SCAN/"):]]
        return params[name]

    def ln(x, pfx):
        mu = jnp.mean(x, -1, keepdims=True)
        var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
        return (x - mu) * jax.lax.rsqrt(var + 1e-5) * p(f"{pfx}/scale") + p(f"{pfx}/bias")

    def proj(x, pfx, bias=True):
        out = x @ p(f"{pfx}/w")
        return out + p(f"{pfx}/b") if bias else out

    ffn = _decode_ffn_fn(proj, swiglu)

    def heads(x, n):
        return x.reshape(x.shape[0], x.shape[1], n, dh).transpose(0, 2, 1, 3)

    def embed(ids, pos0):
        e = jnp.take(p("emb/embedding/word_emb"), ids, axis=0) * (D ** 0.5)
        if rope:
            return e
        return e + jax.lax.dynamic_slice_in_dim(pe, pos0, ids.shape[1], axis=0)

    def rotate(x, pos0):  # pre-rotated K cache (see generate())
        t = x.shape[2]
        cos = jax.lax.dynamic_slice_in_dim(rope_cos, pos0, t, axis=0)
        sin = jax.lax.dynamic_slice_in_dim(rope_sin, pos0, t, axis=0)
        return apply_rope(x, cos, sin)

    def attn_vs_cache(q, kc_l, vc_l, t):
        # q [N, H, 1, dh]; kc_l/vc_l [N, H_kv, T_max, dh]; attend over [0, t]
        n = q.shape[0]
        qg = q.reshape(n, H_kv, G, 1, dh)
        s = jnp.einsum("bkgqd,bktd->bkgqt", qg, kc_l) * scale
        live = _live_mask(T_max, t, window)
        s = jnp.where(live[None, None, None, None, :], s, -1e9)
        o = jnp.einsum("bkgqt,bktd->bkgqd", jax.nn.softmax(s, -1), vc_l)
        return o.reshape(n, H, 1, dh)

    def block(x, i, attend, pos0=0):
        pfx = f"layer_{i}/self_attn"
        q = heads(proj(x, f"{pfx}/q"), H)
        k = heads(proj(x, f"{pfx}/k"), H_kv)
        v = heads(proj(x, f"{pfx}/v"), H_kv)
        if rope:
            q = rotate(q, pos0)
            k = rotate(k, pos0)
        ctx = attend(q, k, v, i)
        ctx = ctx.transpose(0, 2, 1, 3).reshape(x.shape[0], x.shape[1], D)
        x = ln(x + proj(ctx, f"{pfx}/out"), f"layer_{i}/layer_norm")
        return ln(x + ffn(x, i), f"layer_{i}/layer_norm_1")

    def logits_of(x_last):
        return ln(x_last, "layer_norm") @ p("project/logits/w")

    def run_layer_scan(x0, kc, vc, pos0, make_attend):
        """generate()'s scanned layer loop, beam cache layout (layer axis at
        dim 1): repopulate the scan_view overlay per slice, carry caches."""
        def body(carry, sl):
            y, kc, vc = carry
            scan_view.clear()
            scan_view.update(sl["p"])
            li = sl["i"]

            def attend(q, k, v, _i):
                nonlocal kc, vc
                ctx, kc, vc = make_attend(q, k, v, li, kc, vc)
                return ctx

            y = block(y, "SCAN", attend, pos0=pos0)
            return (y, kc, vc), None

        return jax.lax.scan(
            body, (x0, kc, vc), {"p": stacked, "i": jnp.arange(L)}
        )[0]

    # --- prefill positions [0, Tp-1): full causal pass over the prompt head
    from paddle_tpu.ops.attention import scaled_dot_product_attention

    cdt = cache_dtype or jnp.float32  # bf16 halves decode HBM traffic
    kc0 = jnp.zeros((B, L, H_kv, T_max, dh), cdt)
    vc0 = jnp.zeros((B, L, H_kv, T_max, dh), cdt)
    caches = {"k": kc0, "v": vc0}
    Thead = Tp - 1
    if Thead > 0 and scan_layers:
        def prefill_write(q, k, v, li, kc, vc):
            kc = jax.lax.dynamic_update_slice(
                kc, k[:, None].astype(cdt), (0, li, 0, 0, 0)
            )
            vc = jax.lax.dynamic_update_slice(
                vc, v[:, None].astype(cdt), (0, li, 0, 0, 0)
            )
            ctx = scaled_dot_product_attention(q, k, v, causal=True, window=window)
            return ctx, kc, vc

        x, kc_f, vc_f = run_layer_scan(
            embed(prompt[:, :Thead], 0), kc0, vc0, 0, prefill_write
        )
        caches = {"k": kc_f, "v": vc_f}
    elif Thead > 0:
        def prefill_attend(q, k, v, i):
            caches["k"] = caches["k"].at[:, i, :, :Thead].set(k.astype(cdt))
            caches["v"] = caches["v"].at[:, i, :, :Thead].set(v.astype(cdt))
            # flash-capable prefill, exactly as in generate()
            return scaled_dot_product_attention(q, k, v, causal=True, window=window)

        x = embed(prompt[:, :Thead], 0)
        for i in range(L):
            x = block(x, i, prefill_attend, pos0=0)

    # --- beam decode: carry leaves are [B, ...] (beam_search tiles dim 0)
    init_carry = {"k": caches["k"], "v": caches["v"],
                  "t": jnp.full((B,), Thead, jnp.int32)}

    def step_fn(carry, tokens):
        t = carry["t"][0]
        xt = embed(tokens[:, None], t)
        kc, vc = carry["k"], carry["v"]

        if scan_layers:
            def cached_attend(q, k, v, li, kc, vc):
                kc = jax.lax.dynamic_update_slice(
                    kc, k[:, None].astype(kc.dtype), (0, li, 0, t, 0)
                )
                vc = jax.lax.dynamic_update_slice(
                    vc, v[:, None].astype(vc.dtype), (0, li, 0, t, 0)
                )
                kci = jax.lax.dynamic_index_in_dim(kc, li, 1, keepdims=False)
                vci = jax.lax.dynamic_index_in_dim(vc, li, 1, keepdims=False)
                return attn_vs_cache(q, kci, vci, t), kc, vc

            y, kc, vc = run_layer_scan(xt, kc, vc, t, cached_attend)
        else:
            def attend(q, k, v, i):
                nonlocal kc, vc
                kc = jax.lax.dynamic_update_slice(kc, k[:, None].astype(kc.dtype), (0, i, 0, t, 0))
                vc = jax.lax.dynamic_update_slice(vc, v[:, None].astype(vc.dtype), (0, i, 0, t, 0))
                return attn_vs_cache(q, kc[:, i], vc[:, i], t)

            y = xt
            for i in range(L):
                y = block(y, i, attend, pos0=t)
        logp = jax.nn.log_softmax(logits_of(y[:, -1]).astype(jnp.float32), -1)
        return {"k": kc, "v": vc, "t": carry["t"] + 1}, logp

    return ocf.beam_search(
        step_fn,
        init_carry,
        batch_size=B,
        beam_size=beam_size,
        vocab_size=cfg["vocab"],
        max_len=max_new_tokens,
        bos_id=prompt[:, -1],
        eos_id=eos_id,
        length_penalty_alpha=length_penalty_alpha,
    )
