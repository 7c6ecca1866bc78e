"""The hybrid Mamba-2 / attention LM (``models/hybrid_ssm_lm.py``) at a small
size on the CPU: the three forms of the SSM core agree with each other and
with the reference's plain recurrence; ``pt.Trainer`` trains the model with
the reference's loss and gradients; prefill and decoding through the mixed
cache (pages and states in one engine) give the reference's full forward
pass, on logits and on served tokens, through slot reuse, preemption and the
recovery ladder; and what needs a state snapshot is refused by name."""

import os
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import models
from paddle_tpu.models import hybrid_ssm_lm as hm
from paddle_tpu.observability import metrics as obs_metrics
from paddle_tpu.ops.pallas import ssm as kernel
from paddle_tpu.resilience import faults
from paddle_tpu.serving import DecodeConfig, DecodeEngine
from paddle_tpu.serving.disagg import PREFILL, DisaggRouter
from paddle_tpu.serving.host_tier import HostPagePool

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from benchmarks import check  # noqa: E402
from benchmarks.references import common as refc  # noqa: E402
from benchmarks.references import hybrid_ssm_lm as ref  # noqa: E402

VOCAB = 97
# granite-4.0-h-micro's block in small: the four multipliers, NoPE, a tied
# head, both kinds of layer; a state that outlives a chunk and a request
# (dt near 0.05 x |A| near 1: a fifth of it is left after 32 tokens), taps
# and queries large enough that the tail and the planes weigh something
SMALL = dict(vocab=VOCAB, d_model=64, d_inner=128, num_heads=4, num_kv_heads=2, head_dim=16,
             ssm_heads=4, ssm_head_dim=16, ssm_state=8, ssm_chunk=4,
             layer_types=("mamba", "attention", "mamba", "mamba", "attention"),
             embedding_multiplier=12.0, residual_multiplier=0.22, attention_multiplier=0.0625,
             logits_scaling=8.0, ssm_dt_shift=-3.0, ssm_conv_gain=4.0, attn_q_gain=8.0,
             branch_gain=8.0,
             param_dtype="float32", compute_dtype="float32")
DECODE = dict(max_slots=3, page_size=4, max_context=64, prefill_chunk=8)


def _lm(**over):
    spec = models.get_model("hybrid_ssm_lm", seq_len=16, **dict(SMALL, **over))
    ids, labels = spec.synth_batch(2, np.random.RandomState(0))
    variables = spec.model.init(0, ids, labels)
    # the framework's own start puts every tap at N(0, 0.3) and dt at 0.01:
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


def _params(lm):
    return {k: jnp.asarray(v) for k, v in lm.variables.params.items()}


def reference_logits(lm, ids):
    with jax.default_matmul_precision("highest"):
        return np.asarray(ref.logits_fn(_params(lm), jnp.asarray(ids), lm.cfg, refc.mm_f32))


def gap_to_reference(lm, prompt, tokens) -> float:
    """How far, in standard deviations of a position's logits, the served
    tokens lie below the best of the reference's one full pass over prompt
    and served tokens (``check.gap_sigmas``: what decides ``correct``)."""
    logits = reference_logits(lm, np.concatenate([prompt, tokens])[None])[0]
    rows = logits[len(prompt) - 1:len(prompt) - 1 + len(tokens)]
    return float(check.gap_sigmas(rows, tokens).max())


# -- (a) the SSM core: recurrence = chunked form = one-token step -------------

@pytest.mark.parametrize("decays, G", [("near_0", 1), ("near_1", 1), ("mixed", 1),
                                       ("mixed", 2), ("mixed", 8)])
def test_the_three_forms_of_the_core_agree(decays, G):
    T, H, P, N, Q = 22, 3 if G == 1 else 8, 8, 16, 8  # two whole blocks and one of 6, padded to 8
    D = H * P
    rng = np.random.default_rng(11)
    f32 = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    x, b, c, h0 = f32(T, D), f32(T, G, N), f32(T, G, N), f32(N, D)
    a_neg = -jnp.exp(0.3 * f32(H))
    dt = {"near_0": rng.uniform(2.0, 6.0, (T, H)), "near_1": rng.uniform(1e-4, 2e-3, (T, H)),
          "mixed": np.exp(rng.uniform(np.log(1e-3), np.log(3.0), (T, H)))}[decays]
    dt = jnp.asarray(dt, jnp.float32)
    with jax.default_matmul_precision("highest"):
        y_scan, h_scan = hm.ssm_scan(x, dt, a_neg, b, c, h0)
        y_one, h_one = hm.ssm_chunked(x, dt, a_neg, b, c, h0, chunk=T)  # one block
        # chunk by chunk as the engine's prefill walks a prompt: the state is
        # handed on, the last chunk is padded and a padded position has dt 0
        pad = -T % Q
        xp, bp, cp = (jnp.pad(v, ((0, pad),) + ((0, 0),) * (v.ndim - 1), constant_values=7.0)
                      for v in (x, b, c))
        dtp = jnp.pad(dt, ((0, pad), (0, 0)))
        ys, h = [], h0
        for at in range(0, T + pad, Q):
            y, h = hm.ssm_chunked(xp[at:at + Q], dtp[at:at + Q], a_neg, bp[at:at + Q],
                                  cp[at:at + Q], h, chunk=4)  # two blocks a chunk
            ys.append(y)
        y_chunks = jnp.concatenate(ys)[:T]
        # a token at a time through the decode step's twin, one slot of two active
        state = jnp.stack([h0, h0])[None]  # [1, 2, N, D]
        on = jnp.asarray([1, 0])
        y_step = []
        for t in range(T):
            two = lambda v: jnp.stack([v, v])
            y, state = kernel.ssm_step_xla(
                state, two(hm._by_channel(dt[t], P) * x[t]),
                two(hm._by_channel(jnp.exp(dt[t] * a_neg), P)), two(b[t]), two(c[t]), on, layer=0)
            y_step.append(y[0])
    # float32 sums in other orders; exp of a cumulated log against a running product
    tol = dict(rtol=2e-4, atol=2e-4)
    for got in (y_one, y_chunks, jnp.stack(y_step)):
        np.testing.assert_allclose(got, y_scan, **tol)
    for got in (h_one, h, state[0, 0]):
        np.testing.assert_allclose(got, h_scan, **tol)
    assert (np.asarray(state[0, 1]) == np.asarray(h0)).all()  # the idle slot


def test_the_models_forward_is_the_references_recurrence(lm):
    """Training's chunked form over five blocks of 4 and the attention layers'
    full softmax against the reference's plain recurrence, on logits."""
    ids = np.random.RandomState(1).randint(1, VOCAB, size=(2, 19)).astype(np.int32)
    (_, _, logits), _ = lm.spec.model.apply(lm.variables, ids, ids)
    want = reference_logits(lm, ids)
    # float32 both sides; the logits are of order 1
    np.testing.assert_allclose(logits, want, rtol=1e-4, atol=2e-5)


# -- (b) training: the reference's loss and gradients --------------------------

def test_trainer_loss_and_gradients_are_the_references(lm):
    rng = np.random.RandomState(4)
    ids = rng.randint(1, VOCAB, size=(2, 16)).astype(np.int32)
    labels = rng.randint(1, VOCAB, size=(2, 16)).astype(np.int32)
    params = _params(lm)
    with jax.default_matmul_precision("highest"):
        loss_fn = lambda p: ref.loss_sum(p, jnp.asarray(ids), jnp.asarray(labels), lm.cfg,
                                         refc.mm_f32) / ids.size
        want_loss, want_grad = jax.value_and_grad(loss_fn)(params)
    trainer = pt.Trainer(lambda: lm.spec.model, lambda: pt.optimizer.SGD(learning_rate=1.0))
    # the step consumes the state it is handed: the Trainer gets a copy
    trainer.variables = trainer.exe.put(pt.framework.Variables(
        {k: jnp.array(v) for k, v in params.items()}, {}))
    trainer.opt_state = trainer.exe.put(trainer.optimizer.create_state(trainer.variables.params))
    losses = []
    trainer.train(num_epochs=1, reader=lambda: iter([(ids, labels)]),
                  event_handler=lambda ev: losses.append(ev.metrics)
                  if isinstance(ev, pt.trainer.EndStepEvent) else None)
    np.testing.assert_allclose(np.asarray(losses[0]).reshape(-1)[0], want_loss, rtol=1e-5)
    for name, g in want_grad.items():  # SGD at rate 1: the step is the gradient
        got = params[name] - trainer.variables.params[name]
        np.testing.assert_allclose(got, g, rtol=2e-3, atol=2e-6, err_msg=name)


def test_the_model_is_in_the_registry_and_brings_pages_and_states():
    spec = models.get_model("hybrid_ssm_lm", seq_len=8, **{
        k: v for k, v in SMALL.items() if k not in ("param_dtype", "compute_dtype")})
    ids, labels = spec.synth_batch(2, np.random.RandomState(0))
    variables = spec.model.init(0, ids, labels)
    assert {v.dtype for v in variables.params.values()} == {jnp.dtype("bfloat16")}
    assert set(variables.params) == set(hm.param_shapes(spec.extra["cfg"]))
    (loss, _, logits), _ = spec.model.apply(variables, ids, labels)
    assert np.isfinite(float(loss)) and logits.dtype == jnp.float32
    progs = models.serving_programs(spec.extra["cfg"])
    assert progs.cache == "pages+state" and progs.has_pages and progs.has_state
    assert [progs.is_state(a) for a in progs.cache_args] == [False, False, True, True]
    k, v, h, tail = progs.cache_specs(spec.extra["cfg"], max_slots=3, num_pages=9, page_size=4,
                                      dtype=jnp.bfloat16)
    # planes are a layer's ordinal among its kind: 2 attention, 3 Mamba-2 layers
    assert k.shape == v.shape == (2, 9, 4, 32) and k.dtype == jnp.bfloat16
    assert h.shape == (3, 3, 8, 64) and tail.shape == (3, 3, 3 * 80)
    assert h.dtype == tail.dtype == jnp.float32
    with pytest.raises(Exception, match="layers of both kinds"):
        models.get_model("hybrid_ssm_lm", layer_types=("mamba", "mamba"))
    with pytest.raises(Exception, match="ssm_groups 3"):
        models.get_model("hybrid_ssm_lm", ssm_groups=3)


def test_two_groups_of_b_and_c_run_against_the_scan(monkeypatch):
    """``ssm_groups`` 2 (refused until PR 45): the model's forward through the
    chunked form against the same forward through the plain recurrence
    (``ssm_scan``, which ``tests/test_ssm_kernel.py`` holds to the recurrence
    spelled a channel at a time), on logits; the groups matter (every head
    reading group 0's B and C gives other logits). The reference of this
    family has one group; ``references/hybrid_moe_lm.py`` has them."""
    two = _lm(ssm_groups=2)
    assert two.cfg["ssm_groups"] == 2
    assert two.variables.params["layer_0/mamba/in/w"].shape == (64, 64 + (64 + 2 * 2 * 8) + 4)
    ids = np.random.RandomState(1).randint(1, VOCAB, size=(2, 19)).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        (_, _, logits), _ = two.spec.model.apply(two.variables, ids, ids)
        chunked = hm.ssm_chunked
        monkeypatch.setattr(hm, "ssm_chunked",
                            lambda x, dt, a, b, c, h0, **_: hm.ssm_scan(x, dt, a, b, c, h0))
        (_, _, want), _ = two.spec.model.apply(two.variables, ids, ids)
        first = lambda v: jnp.repeat(v[..., :1, :], v.shape[-2], axis=-2)
        monkeypatch.setattr(hm, "ssm_chunked", lambda x, dt, a, b, c, h0, **kw: chunked(
            x, dt, a, first(b), first(c), h0, **kw))
        (_, _, wrong), _ = two.spec.model.apply(two.variables, ids, ids)
    # float32 both sides, the logits of order 1: sums in other orders
    np.testing.assert_allclose(logits, want, rtol=1e-4, atol=2e-5)
    assert np.abs(np.asarray(wrong) - np.asarray(want)).max() > 1e-2


# -- (c) prefill then decode through the mixed cache, on logits ---------------

def _walk(lm, prompt, n_new, slot=1, chunk=8, page=4, state_dtype=None, stream_dtype=None):
    """The two serving programs' bodies by hand, keeping the logits they
    sample from: ``prompt`` in chunks of ``chunk`` into slot ``slot`` of three
    (the last chunk padded), then ``n_new`` decode steps fed the reference's
    own next tokens. ``state_dtype`` rounds the SSM state after every write,
    ``stream_dtype`` the residual stream after every block: what a lower
    precision than the configuration states would do."""
    cfg, p = lm.cfg, hm._params_of(lm.variables)
    S, P = 3, 16
    specs = hm.hybrid_cache_specs(cfg, max_slots=S, num_pages=1 + S * P, page_size=page,
                                  dtype=jnp.float32)
    # garbage everywhere: a chunk at position 0 must start the slot over
    cache = hm._cache_in(*(jnp.full(s.shape, 3.0, s.dtype) for s in specs))
    tables = np.zeros((S, P), np.int32)
    tables[slot] = 1 + slot * P + np.arange(P)
    rounded = lambda x, dt: x if dt is None else x.astype(dt).astype(x.dtype)
    real_block = hm.block

    def block(p_, x, i, cfg_, via):
        out = rounded(real_block(p_, x, i, cfg_, via), stream_dtype)
        cache["ssm_state"] = rounded(cache["ssm_state"], state_dtype)
        return out

    hm.block = block
    try:
        rows, seq = [], list(prompt)
        for c0 in range(0, len(prompt), chunk):
            toks = np.zeros((chunk,), np.int32)
            seg = prompt[c0:c0 + chunk]
            toks[:len(seg)] = seg
            last = len(prompt) - 1 - c0
            via = hm._via_chunk(cfg, cache, jnp.asarray(tables[slot]), jnp.int32(slot),
                                jnp.int32(c0), jnp.int32(last), chunk, page)
            x = hm._hidden(p, jnp.asarray(toks)[None], cfg, via)
            rows.extend(np.asarray(hm._logits(p, x[0], cfg))[:len(seg)])
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
            x = hm._hidden(p, jnp.asarray(tokens)[:, None], cfg, via)
            rows.append(np.asarray(hm._logits(p, x[:, 0], cfg))[slot])
    finally:
        hm.block = real_block
    seq = np.asarray(seq, np.int32)
    return np.stack(rows), reference_logits(lm, seq[None])[0], cache


# float32 both sides, sums in other orders, through five blocks: logits of
# order 1 (largest 1.14, standard deviation 0.16) agree to 4e-7 here; eight
# times that. The SSM state rounded to bfloat16 after every write reads 1.3e-5
# and the residual stream rounded after every block 2.3e-3
LOGIT_TOL = 3e-6


def test_prefill_and_decode_through_the_mixed_cache_give_the_references_logits(lm):
    prompt = np.random.RandomState(6).randint(1, VOCAB, size=(21,)).astype(np.int32)
    got, want, cache = _walk(lm, prompt, n_new=9)  # chunks of 8, 8, 5 + 3 padded
    assert np.abs(got - want).max() < LOGIT_TOL
    # the other slots' states and tails still hold what they held
    for name in ("ssm_state", "conv_state"):
        arr = np.asarray(cache[name])
        assert (arr[:, [0, 2]] == 3.0).all() and (arr[:, 1] != 3.0).any(), name


@pytest.mark.parametrize("what", ["state_dtype", "stream_dtype"])
def test_a_bfloat16_state_or_residual_stream_would_fail_the_tolerance(lm, what):
    prompt = np.random.RandomState(6).randint(1, VOCAB, size=(21,)).astype(np.int32)
    got, want, _ = _walk(lm, prompt, n_new=9, **{what: jnp.bfloat16})
    assert np.abs(got - want).max() > 3 * LOGIT_TOL


# -- (d) the engine: served tokens, slot reuse, preemption, the recovery ladder

def _cases(seed, shapes):
    rng = np.random.RandomState(seed)
    return [(rng.randint(1, VOCAB, size=(n,)).astype(np.int32), m) for n, m in shapes]


def test_served_tokens_are_the_references_through_admission_prefill_and_a_step_fault(lm):
    # six requests on three slots: slots are freed and taken again mid-run; the
    # 30- and 27-token prompts prefill (4 chunks) while the other slots decode
    cases = _cases(5, [(5, 9), (30, 6), (9, 12), (27, 5), (3, 4), (14, 7)])
    eng = DecodeEngine(lm.variables, lm.cfg, decode=DecodeConfig(**DECODE))
    try:
        with faults.injected(faults.FaultSpec(faults.DECODE_STEP, "error", after=3, times=1)):
            outs = [h.result(timeout=300) for h in [eng.submit(p, m) for p, m in cases]]
        snap = eng.metrics.snapshot()
    finally:
        eng.close()
    eng.kv.assert_no_leaks()
    assert snap["step_faults_total"] == 1 and snap["recovered_total"] >= 1
    assert eng.decode_step_cache_size() == 1 and eng.prefill_cache_size() == 1
    for (prompt, budget), out in zip(cases, outs):
        assert out.finish_reason == "length" and len(out.tokens) == budget
        assert gap_to_reference(lm, prompt, out.tokens) < 1e-3, len(prompt)
    label = {"engine": eng.metrics.engine_label}
    reg = obs_metrics.default_registry()
    get = lambda name: reg.get(f"serving.decode.{name}", label, default=None)
    # states of 3 Mamba-2 layers x 3 slots: [8, 64] and a tail of 3 x 80, float32
    assert get("state_bytes") == 3 * 3 * (8 * 64 + 3 * 80) * 4
    # a K and a V row of 2 heads of 16 in each of 2 attention layers, float32
    assert get("cache_bytes_per_token") == 2 * 2 * 32 * 4
    assert get("state_slots_in_use") == 0.0 and get("pages_in_use") == 0.0
    assert get("pages_donated") == 1.0 and get("state_donated") == 1.0
    assert get("ssm.layers") == 3


def test_two_requests_through_one_slot_in_turn_start_it_over(lm):
    """One slot: the second request finds the first one's tails, states and
    pages there, and is served as if the slot were new."""
    cases = _cases(7, [(19, 8), (11, 8), (4, 6)])
    eng = DecodeEngine(lm.variables, lm.cfg, decode=DecodeConfig(
        max_slots=1, page_size=4, max_context=64, prefill_chunk=8))
    try:
        outs = [eng.infer(p, m) for p, m in cases]
    finally:
        eng.close()
    eng.kv.assert_no_leaks()
    for (prompt, _), out in zip(cases, outs):
        assert gap_to_reference(lm, prompt, out.tokens) < 1e-3, len(prompt)


def test_a_preempted_request_loses_pages_and_state_and_comes_back_token_exact(lm):
    """A starved page pool (13 usable pages against the 30 three grown slots
    want): the engine preempts, the victim's pages go back to the pool, its
    slot (and so its state) to the next admission, and it prefills again from
    position 0, prompt and generated tokens, to the same answer."""
    cases = _cases(9, [(18, 14), (20, 12), (11, 16), (6, 10)])
    want = []
    for prompt, budget in cases:  # each alone on an engine with room
        eng = DecodeEngine(lm.variables, lm.cfg, decode=DecodeConfig(**DECODE))
        try:
            want.append(eng.infer(prompt, budget).tokens)
        finally:
            eng.close()
    eng = DecodeEngine(lm.variables, lm.cfg, decode=DecodeConfig(
        max_slots=3, page_size=4, max_context=40, prefill_chunk=8, num_pages=14))
    try:
        outs = [h.result(timeout=300) for h in [eng.submit(p, m) for p, m in cases]]
        snap = eng.metrics.snapshot()
    finally:
        eng.close()
    eng.kv.assert_no_leaks()
    assert snap["preempted_total"] >= 1 and snap["resumed_total"] == snap["preempted_total"]
    assert eng.decode_step_cache_size() == 1 and eng.prefill_cache_size() == 1
    for (prompt, _), out, ref_tokens in zip(cases, outs, want):
        assert np.array_equal(out.tokens, ref_tokens), len(prompt)
        assert gap_to_reference(lm, prompt, out.tokens) < 1e-3


@pytest.mark.parametrize("jit", ["_step", "_prefill"])
def test_every_cache_writing_jit_consumes_all_four_arrays(lm, jit):
    eng = DecodeEngine(lm.variables, lm.cfg, decode=DecodeConfig(**DECODE))
    try:
        real = getattr(eng, jit)
        seen = []

        def spy(*args):
            out = real(*args)
            held = [a for a in args if getattr(a, "ndim", 0) >= 3 and hasattr(a, "is_deleted")]
            seen.append([a.is_deleted() for a in held])
            return out

        spy._cache_size = real._cache_size
        setattr(eng, jit, spy)
        for p, m in _cases(2, [(11, 4), (4, 6)]):
            eng.infer(p, m)
        kinds = [(c.ndim, str(c.dtype)) for c in eng._cache]
    finally:
        eng.close()
    assert seen and all(s == [True] * 4 for s in seen), seen[:3]
    assert kinds == [(4, "float32"), (4, "float32"), (4, "float32"), (3, "float32")]


def test_a_step_that_fails_after_consuming_the_arrays_is_recovered_by_re_prefill(lm):
    """The donated call dies having eaten pages and states: the engine
    rebuilds all four arrays zeroed and every request prefills again, to the
    same tokens."""
    cases = _cases(8, [(12, 8), (6, 8)])
    eng = DecodeEngine(lm.variables, lm.cfg, decode=DecodeConfig(**DECODE))
    try:
        real, calls = eng._step, []

        def dies_once(*args):
            out = real(*args)
            calls.append(1)
            if len(calls) == 3:
                raise RuntimeError("injected: failed after the call consumed the arrays")
            return out

        eng._step = dies_once
        outs = [h.result(timeout=300) for h in [eng.submit(p, m) for p, m in cases]]
        snap = eng.metrics.snapshot()
    finally:
        eng.close()
    assert snap["step_faults_total"] == 1
    for (p, m), out in zip(cases, outs):
        assert len(out.tokens) == m and gap_to_reference(lm, p, out.tokens) < 1e-3


# -- (e) what needs a state snapshot is refused, by name ----------------------

@pytest.mark.parametrize("feature, kwargs", [
    ("the prefix cache", dict(decode=DecodeConfig(prefix_cache=True, **DECODE))),
    ("the host tier", dict(decode=DecodeConfig(host_tier_bytes=1 << 20, **DECODE))),
    ("the host tier", dict(decode=DecodeConfig(**DECODE), host_tier=HostPagePool(1 << 20, 4))),
    ("a draft model", dict(decode=DecodeConfig(**DECODE), draft_variables="same")),
    ("a replica group", dict(decode=DecodeConfig(**DECODE), group="one")),
])
def test_the_engine_refuses_what_needs_a_state_snapshot(lm, feature, kwargs):
    if kwargs.get("draft_variables") == "same":
        kwargs = dict(kwargs, draft_variables=lm.variables)
    if kwargs.get("group") == "one":
        from paddle_tpu.serving.shardgroup import make_groups

        kwargs = dict(kwargs, group=make_groups(2)[0])
    with pytest.raises(Exception, match=f"{feature} cannot be used.*Mamba-2 layers.*snapshots"):
        DecodeEngine(lm.variables, lm.cfg, **kwargs)


def test_disaggregated_handoff_is_refused(lm):
    engines = [DecodeEngine(lm.variables, lm.cfg, decode=DecodeConfig(**DECODE))
               for _ in range(2)]
    try:
        with pytest.raises(Exception, match="disaggregated handoff cannot be used.*Mamba-2"):
            DisaggRouter(engines, [PREFILL, "decode"])
        with pytest.raises(Exception, match="disaggregated handoff cannot be used"):
            engines[1].adopt_handoff(None)
    finally:
        for e in engines:
            e.close()
