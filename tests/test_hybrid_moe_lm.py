"""The single-mixer Mamba-2 / attention / latent-expert LM
(``models/hybrid_moe_lm.py``) at a small size on the CPU: the training forward,
then prefill and decoding through the mixed cache, give the plain reference's
full forward pass on logits (one chunk, several chunks, a slot taken again
after another request); ``pt.Trainer`` trains it; the gated norm by group is
not the norm over all channels; four shares of the router's width add up to
the uncut layer; and what the engine cannot do for a model that keeps states
is refused by name."""

import os
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import models
from paddle_tpu.models import hybrid_moe_lm as hmm
from paddle_tpu.models import hybrid_ssm_lm as hm
from paddle_tpu.observability import metrics as obs_metrics
from paddle_tpu.serving import DecodeConfig, DecodeEngine

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from benchmarks import check  # noqa: E402
from benchmarks.references import common as refc  # noqa: E402
from benchmarks.references import hybrid_moe_lm as ref  # noqa: E402
from benchmarks.tiny_experts import as_checkpoint  # noqa: E402

VOCAB = 97
# Nemotron 3 Super's layers in small: one mixer a layer, all three kinds, two
# groups of B and C, GQA, a 16-wide sigmoid router with 4 a token over latent
# relu^2 experts, one shared expert; a state that outlives a chunk and a
# request, taps and queries large enough that the tail and the keys weigh
SMALL = dict(vocab=VOCAB, d_model=64, pattern="*EMEM", num_heads=4, num_kv_heads=2, head_dim=16,
             ssm_heads=8, ssm_head_dim=16, ssm_state=8, ssm_groups=2, ssm_chunk=4,
             num_experts=16, experts_per_token=4, moe_latent=32, moe_d_inner=48,
             shared_d_inner=96, routed_scaling=5.0, ssm_dt_shift=-3.0, ssm_conv_gain=4.0,
             attn_q_gain=4.0, param_dtype="float32", compute_dtype="float32")
DECODE = dict(max_slots=3, page_size=4, max_context=64, prefill_chunk=8)


def _lm(**over):
    spec = models.get_model("hybrid_moe_lm", seq_len=16, **dict(SMALL, **over))
    ids, labels = spec.synth_batch(2, np.random.RandomState(0))
    variables = spec.model.init(0, ids, labels)
    # noise on the small leaves, as the benchmark's seeded weights have it
    rng = np.random.RandomState(3)
    params = {k: (v + 0.1 * rng.standard_normal(v.shape).astype(np.float32)
                  if k.rsplit("/", 1)[-1] in ("b", "bias", "scale") else v)
              for k, v in variables.params.items()}
    return types.SimpleNamespace(variables=pt.framework.Variables(params, {}), spec=spec,
                                 cfg=spec.extra["cfg"])


@pytest.fixture(scope="module")
def lm():
    return _lm()


def _reference_params(lm):
    """The program's parameters as the reference reads them: a matrix an expert."""
    return as_checkpoint({k: jnp.asarray(v) for k, v in lm.variables.params.items()},
                         hmm.held_experts(lm.cfg))


PAD_TO = 48  # every sequence is padded to one length: the reference compiles once a model


def reference_logits(lm, ids):
    """The reference's logits for ``ids`` [B, T]; every layer is causal, so
    the padding behind a sequence reaches none of its positions."""
    if not hasattr(lm, "reference"):
        cfg = lm.cfg
        lm.reference = jax.jit(lambda p, x: ref.logits_fn(p, x, cfg, refc.mm_f32))
    ids = np.asarray(ids)
    padded = np.zeros((ids.shape[0], PAD_TO), np.int32)
    padded[:, :ids.shape[1]] = ids
    with jax.default_matmul_precision("highest"):
        return np.asarray(lm.reference(_reference_params(lm), jnp.asarray(padded)))[:, :ids.shape[1]]


def gap_to_reference(lm, prompt, tokens) -> float:
    logits = reference_logits(lm, np.concatenate([prompt, tokens])[None])[0]
    rows = logits[len(prompt) - 1:len(prompt) - 1 + len(tokens)]
    return float(check.gap_sigmas(rows, tokens).max())


# -- (a) training --------------------------------------------------------------

def test_the_training_forward_is_the_references(lm):
    """The chunked form over five blocks of 4, full attention and the ragged
    dot against the reference's recurrence and its loop over experts."""
    ids = np.random.RandomState(1).randint(1, VOCAB, size=(2, 19)).astype(np.int32)
    (_, _, logits), _ = lm.spec.model.apply(lm.variables, ids, ids)
    # float32 both sides; the logits are of order 1
    np.testing.assert_allclose(logits, reference_logits(lm, ids), rtol=1e-4, atol=2e-5)


def test_trainer_trains_the_model_and_the_loss_is_the_references(lm):
    rng = np.random.RandomState(4)
    ids = rng.randint(1, VOCAB, size=(2, 16)).astype(np.int32)
    labels = rng.randint(1, VOCAB, size=(2, 16)).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        want = ref.loss_sum(_reference_params(lm), jnp.asarray(ids), jnp.asarray(labels), lm.cfg,
                            refc.mm_f32) / ids.size
    trainer = pt.Trainer(lambda: lm.spec.model, lambda: pt.optimizer.Adam(learning_rate=3e-3))
    trainer.variables = trainer.exe.put(pt.framework.Variables(
        {k: jnp.array(v) for k, v in lm.variables.params.items()}, {}))
    trainer.opt_state = trainer.exe.put(trainer.optimizer.create_state(trainer.variables.params))
    losses = []
    trainer.train(num_epochs=1, reader=lambda: iter([(ids, labels)] * 6),
                  event_handler=lambda ev: losses.append(float(np.asarray(ev.metrics).reshape(-1)[0]))
                  if isinstance(ev, pt.trainer.EndStepEvent) else None)
    np.testing.assert_allclose(losses[0], want, rtol=1e-5)
    assert losses[-1] < losses[0] - 0.05  # the same batch six times: it is learnt
    moved = [k for k, v in trainer.variables.params.items()
             if np.abs(np.asarray(v) - np.asarray(lm.variables.params[k])).max() > 0]
    # the router's selection bias takes no gradient; every other leaf moved
    assert sorted(set(lm.variables.params) - set(moved)) == sorted(
        k for k in lm.variables.params if k.endswith("/router/b"))


def test_the_model_is_in_the_registry_and_brings_pages_states_and_two_extras():
    spec = models.get_model("hybrid_moe_lm", seq_len=8, **{
        k: v for k, v in SMALL.items() if k not in ("param_dtype", "compute_dtype")})
    ids, labels = spec.synth_batch(2, np.random.RandomState(0))
    variables = spec.model.init(0, ids, labels)
    cfg = spec.extra["cfg"]
    assert {v.dtype for v in variables.params.values()} == {jnp.dtype("bfloat16")}
    assert set(variables.params) == set(hmm.param_shapes(cfg))
    assert cfg["layer_types"] == ("attention", "moe", "mamba", "moe", "mamba")
    progs = models.serving_programs(cfg)
    assert progs.cache == "pages+state" and progs.extras == ("active", "expert_load")
    assert [progs.is_state(a) for a in progs.cache_args] == [False, False, True, True]
    k, v, h, tail = progs.cache_specs(cfg, max_slots=3, num_pages=9, page_size=4,
                                      dtype=jnp.bfloat16)
    # one attention plane, two Mamba-2 planes; a tail holds x, B and C of both groups
    assert k.shape == v.shape == (1, 9, 4, 32) and h.shape == (2, 3, 8, 128)
    assert tail.shape == (2, 3, 3 * (128 + 2 * 2 * 8))
    assert progs.gauges(cfg) == {"ssm.layers": 2, "ssm.state_bytes_a_slot": 2 * 4 * 8 * 128,
                                 "moe.experts_held": 16, "moe.router_width": 16}
    attrs = progs.span_attrs(cfg, np.array([1, 0, 1]), np.array([[2, 0, 1], [0, 0, 3]]))
    assert attrs == {"ssm_active_slots": 2, "ssm_layers": 2,
                     "ssm_state_bytes_moved": 2 * 2 * 2 * 4 * 8 * 128,
                     "moe_pairs": 6, "moe_experts_hit": 3, "moe_max_load": 3}
    with pytest.raises(Exception, match="all three kinds"):
        models.get_model("hybrid_moe_lm", pattern="M*M")
    with pytest.raises(Exception, match="pattern 'MXE' may hold"):
        models.get_model("hybrid_moe_lm", pattern="MXE")
    with pytest.raises(Exception, match="experts_held .* is not a range"):
        models.get_model("hybrid_moe_lm", experts_held=(12, 8))


# -- (b) prefill then decode through the mixed cache, on logits ----------------

def _walk(lm, prompt, n_new, slot=1, chunk=8, page=4, stream_dtype=None, before=None):
    """The two serving programs' bodies by hand, keeping the logits they
    sample from: ``prompt`` in chunks of ``chunk`` into slot ``slot`` of three
    (the last chunk padded), then ``n_new`` decode steps fed the reference's
    own next tokens. ``stream_dtype`` rounds the residual stream after every
    layer. ``before``: a cache another request left (the slot is reused)."""
    cfg, p = lm.cfg, hm._params_of(lm.variables)
    S, P = 3, 16
    specs = hm.hybrid_cache_specs(cfg, max_slots=S, num_pages=1 + S * P, page_size=page,
                                  dtype=jnp.float32)
    # garbage everywhere: a chunk at position 0 must start the slot over
    cache = before or hm._cache_in(*(jnp.full(s.shape, 3.0, s.dtype) for s in specs))
    tables = np.zeros((S, P), np.int32)
    tables[slot] = 1 + slot * P + np.arange(P)
    real_block = hmm.block

    def block(*args, **kw):
        out = real_block(*args, **kw)
        return out if stream_dtype is None else out.astype(stream_dtype).astype(out.dtype)

    hmm.block = block
    try:
        rows, seq = [], list(prompt)
        for c0 in range(0, len(prompt), chunk):
            toks = np.zeros((chunk,), np.int32)
            seg = prompt[c0:c0 + chunk]
            toks[:len(seg)] = seg
            last = len(prompt) - 1 - c0
            via = hm._via_chunk(cfg, cache, jnp.asarray(tables[slot]), jnp.int32(slot),
                                jnp.int32(c0), jnp.int32(last), chunk, page)
            x, _ = hmm._hidden(p, jnp.asarray(toks)[None], cfg, via,
                               routed=jnp.arange(chunk) <= last)
            rows.extend(np.asarray(hmm._logits(p, x[0], cfg))[:len(seg)])
        for _ in range(n_new):
            nxt = int(np.argmax(rows[len(seq) - 1]))
            pos = len(seq)
            seq.append(nxt)
            tokens, positions, on = (np.zeros((S,), np.int32) for _ in range(3))
            tokens[slot], positions[slot], on[slot] = nxt, pos, 1
            step_tables = np.zeros((S, P), np.int32)
            step_tables[slot] = tables[slot]
            via = hm._via_step(cfg, cache, jnp.asarray(step_tables), jnp.asarray(positions),
                               jnp.asarray(on), page)
            x, _ = hmm._hidden(p, jnp.asarray(tokens)[:, None], cfg, via,
                               routed=jnp.asarray(on) != 0)
            rows.append(np.asarray(hmm._logits(p, x[:, 0], cfg))[slot])
    finally:
        hmm.block = real_block
    seq = np.asarray(seq, np.int32)
    return np.stack(rows), reference_logits(lm, seq[None])[0], cache


# float32 both sides, sums in other orders, through five layers: logits of
# order 1 agree to 1e-6 here; several times that. The residual stream rounded
# to bfloat16 after every layer reads over 1e-3
LOGIT_TOL = 8e-6


@pytest.mark.parametrize("n_prompt, n_new", [(7, 5), (21, 9)], ids=["one_chunk", "three_chunks"])
def test_prefill_and_decode_through_the_cache_give_the_references_logits(lm, n_prompt, n_new):
    prompt = np.random.RandomState(6).randint(1, VOCAB, size=(n_prompt,)).astype(np.int32)
    got, want, cache = _walk(lm, prompt, n_new=n_new)  # 21: chunks of 8, 8, 5 + 3 padded
    assert np.abs(got - want).max() < LOGIT_TOL
    for name in ("ssm_state", "conv_state"):  # the other slots keep what they held
        arr = np.asarray(cache[name])
        assert (arr[:, [0, 2]] == 3.0).all() and (arr[:, 1] != 3.0).any(), name


def test_a_slot_taken_again_after_another_request_is_started_over(lm):
    first = np.random.RandomState(7).randint(1, VOCAB, size=(19,)).astype(np.int32)
    _, _, cache = _walk(lm, first, n_new=6)
    second = np.random.RandomState(8).randint(1, VOCAB, size=(11,)).astype(np.int32)
    got, want, _ = _walk(lm, second, n_new=7, before=cache)
    assert np.abs(got - want).max() < LOGIT_TOL


def test_a_bfloat16_residual_stream_would_fail_the_tolerance(lm):
    prompt = np.random.RandomState(6).randint(1, VOCAB, size=(21,)).astype(np.int32)
    got, want, _ = _walk(lm, prompt, n_new=9, stream_dtype=jnp.bfloat16)
    assert np.abs(got - want).max() > 10 * LOGIT_TOL


def test_served_tokens_are_the_references_through_slot_reuse(lm):
    # five requests on three slots: slots are freed and taken again mid-run; the
    # 30-token prompt prefills (4 chunks) while the other slots decode
    rng = np.random.RandomState(5)
    cases = [(rng.randint(1, VOCAB, size=(n,)).astype(np.int32), m)
             for n, m in [(5, 9), (30, 6), (9, 12), (3, 4), (14, 7)]]
    eng = DecodeEngine(lm.variables, lm.cfg, decode=DecodeConfig(**DECODE))
    try:
        outs = [h.result(timeout=300) for h in [eng.submit(p, m) for p, m in cases]]
    finally:
        eng.close()
    eng.kv.assert_no_leaks()
    assert eng.decode_step_cache_size() == 1 and eng.prefill_cache_size() == 1
    for (prompt, budget), out in zip(cases, outs):
        assert out.finish_reason == "length" and len(out.tokens) == budget
        assert gap_to_reference(lm, prompt, out.tokens) < 1e-3, len(prompt)
    label = {"engine": eng.metrics.engine_label}
    get = lambda name: obs_metrics.default_registry().get(
        f"serving.decode.{name}", label, default=None)
    assert get("ssm.layers") == 2 and get("moe.experts_held") == 16
    assert get("moe.router_width") == 16 and get("ssm.state_bytes_a_slot") == 2 * 4 * 8 * 128


# -- (c) the group norm, the share ---------------------------------------------

def test_the_gated_norm_by_group_is_not_the_norm_over_all_channels():
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(5, 64)) * np.repeat([0.2, 3.0], 32), jnp.float32)
    scale = jnp.asarray(1 + 0.1 * rng.normal(size=(64,)), jnp.float32)
    by_group = hm.group_rms_norm(x, scale, 1e-5, 2)
    whole = hm.group_rms_norm(x, scale, 1e-5, 1)
    want = np.concatenate([np.asarray(x[:, g * 32:(g + 1) * 32]) / np.sqrt(
        np.mean(np.square(x[:, g * 32:(g + 1) * 32]), -1, keepdims=True) + 1e-5)
        for g in range(2)], -1) * np.asarray(scale)
    np.testing.assert_allclose(by_group, want, rtol=1e-5, atol=1e-6)
    assert np.abs(np.asarray(by_group) - np.asarray(whole)).max() > 0.5
    # and the model with one group's norm gives other logits than its own
    assert hm.group_rms_norm(x, scale, 1e-5, 1).shape == x.shape


def test_four_shares_of_the_routers_width_add_up_to_the_uncut_layer(lm):
    """The router's 16 outputs split in four shares of 4: the four partial
    results, the shared expert counted once, are the uncut reference's layer
    output."""
    m = "layer_1/moe"
    params = {k: jnp.asarray(v) for k, v in lm.variables.params.items()}
    n = jnp.asarray(np.random.default_rng(9).normal(size=(2, 11, 64)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        parts = []
        for first in (0, 4, 8, 12):
            cfg = dict(lm.cfg, experts_held=(first, 4))
            share = dict(params, **{f"{m}/experts/{w}/w": params[f"{m}/experts/{w}/w"]
                                    [first:first + 4] for w in ("fc1", "fc2")})
            loads = []
            parts.append(hmm.expert_mixer(share.__getitem__, n, m, cfg, loads, kernel=False))
            assert loads[0].shape == (4,)
        loads = []
        shared = hmm.expert_mixer(params.__getitem__, n, m, lm.cfg, loads,
                                  routed=jnp.zeros((22,), bool), kernel=False)
        assert int(loads[0].sum()) == 0  # no routed pair: the shared expert alone
        lp = {k[len("layer_1/"):]: v for k, v in _reference_params(lm).items()
              if k.startswith("layer_1/")}
        uncut = dict(lm.cfg, experts_held=None)
        want = jax.vmap(lambda rows: ref.expert_mixer(rows, lp, uncut, refc.mm_f32))(n)
        one = jax.vmap(lambda rows: ref.expert_share(rows, lp, uncut, refc.mm_f32, held=(4, 4))
                       + ref.shared_expert(rows, lp, refc.mm_f32))(n)
    # float32 both sides, the outputs of order 1
    np.testing.assert_allclose(sum(parts) - 3 * shared, want, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(parts[1], one, rtol=1e-4, atol=1e-5)
    assert np.abs(np.asarray(parts[1] - want)).max() > 0.05  # a share alone is not the layer


def test_a_checkpoint_with_a_matrix_an_expert_is_stacked_at_load(lm):
    cfg = dict(lm.cfg, experts_held=(4, 8))
    held = {k: (v[4:12] if "/experts/" in k else v) for k, v in lm.variables.params.items()}
    loose = as_checkpoint(held, (4, 8))
    assert "layer_1/moe/experts/4/fc1/w" in loose and "layer_1/moe/experts/fc1/w" not in loose
    stacked = hmm.stack_experts(loose, cfg)
    assert not loose and set(stacked) == set(held)
    for k, v in held.items():
        assert np.array_equal(np.asarray(stacked[k]), np.asarray(v)), k


# -- (d) what needs a state snapshot is refused, by name -----------------------

@pytest.mark.parametrize("feature, kwargs", [
    ("the prefix cache", dict(decode=DecodeConfig(prefix_cache=True, **DECODE))),
    ("a draft model", dict(decode=DecodeConfig(**DECODE), draft_variables="same")),
])
def test_the_engine_refuses_what_needs_a_state_snapshot(lm, feature, kwargs):
    if kwargs.get("draft_variables") == "same":
        kwargs = dict(kwargs, draft_variables=lm.variables)
    with pytest.raises(Exception, match=f"{feature} cannot be used.*Mamba-2 layers.*snapshots"):
        DecodeEngine(lm.variables, lm.cfg, **kwargs)
