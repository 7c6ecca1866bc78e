"""paddle_tpu.tune — kernel autotuning store + persistent warmup manifest.

Covers the PR's acceptance contract: the tune store round-trips and
self-invalidates on kernel-fingerprint change, a corrupt/truncated store
degrades to defaults with a runlog alert (never a crash), concurrent
writers can't tear the file (tmp+rename), ``flash_attention`` resolves
blocks store → ``_TUNED_BLOCKS`` → fitted 128/128 with ``tune.cache.*``
counters, T=192-style lengths no longer hard-fail on the 128 default
(largest-MXU-friendly-divisor fallback), and prewarm replays the warmup
manifest without adding compiles — the PR 9 invariant
``decode_step_cache_size() == 1`` holds when the engine starts from the
manifest instead of a full warmup.
"""

import importlib
import json
import os
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.core import profiler as prof
from paddle_tpu.observability.runlog import RunLog, read_runlog, set_runlog
from paddle_tpu.tune import autotune as tune_autotune
from paddle_tpu.tune import search as tune_search
from paddle_tpu.tune import warmup as tune_warmup
from paddle_tpu.tune.store import TuneKey, TuneStore, kernel_fingerprint

# the package __init__ re-exports the flash_attention *function* over the
# submodule name (tests/test_flash_blocks.py documents the same pitfall)
fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")


@pytest.fixture
def tune_env(tmp_path):
    """Route the tune store + warmup manifest into tmp, autotune on, and
    restore/clear all process-level memos afterwards."""
    pt.core.config.set_flags(tune_cache_dir=str(tmp_path), autotune=True)
    tune_autotune.reset_lookup_cache()
    tune_warmup.reset_manifests()
    yield tmp_path
    pt.core.config.set_flags(tune_cache_dir="", autotune=False, prewarm=False)
    tune_autotune.reset_lookup_cache()
    tune_warmup.reset_manifests()


# ---- fit_block: the divisor-fallback policy -------------------------------


def test_fit_block_prefers_mxu_aligned_divisors():
    assert fa.fit_block(128, 1024) == 128       # exact: untouched
    assert fa.fit_block(128, 192) == 96         # largest divisor <= 128
    assert fa.fit_block(256, 384) == 128        # prefers %128 over larger %8
    assert fa.fit_block(512, 384) == 384        # %128-aligned full length
    assert fa.fit_block(128, 130) == 65         # no aligned divisor: largest
    assert fa.fit_block(128, 100) == 100        # block >= total: clamp
    assert fa.fit_block(128, 8) == 8


def test_flash_attention_t192_defaults_no_longer_fail(rng):
    """Pre-fix, T=192 with the 128/128 default tripped the divisibility
    enforce on a perfectly valid input; now the default is fitted."""
    q = jnp.asarray(rng.randn(1, 2, 192, 64).astype(np.float32))
    k = jnp.asarray(rng.randn(1, 2, 192, 64).astype(np.float32))
    v = jnp.asarray(rng.randn(1, 2, 192, 64).astype(np.float32))
    out = fa.flash_attention(q, k, v, causal=True, interpret=True)
    ref = fa._reference_attention(q, k, v, True, 64 ** -0.5)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_candidate_blocks_always_valid_never_empty():
    for t_q, t_kv in ((256, 256), (192, 192), (1024, 4096), (130, 130)):
        cands = tune_search.candidate_blocks(t_q, t_kv, 64)
        assert cands, (t_q, t_kv)
        for bq, bk in cands:
            assert t_q % bq == 0 and t_kv % bk == 0, (t_q, t_kv, bq, bk)
    # MXU-friendly lengths only produce lane-aligned candidates
    assert all(bq % 128 == 0 and bk % 128 == 0
               for bq, bk in tune_search.candidate_blocks(1024, 1024, 64))


@pytest.mark.parametrize("kernel", ["fwd", "dkv", "dq"])
def test_candidates_follow_each_kernels_working_set(kernel):
    """Candidates reach 1024, are sized by the kernel's own arithmetic (not a
    copy of the forward's), and a head size that makes the large tiles too
    big drops them for that kernel only."""
    assert max(tune_search.CANDIDATE_SIZES) == 1024
    cands = tune_search.candidate_blocks(2048, 2048, 64, kernel)
    assert (1024, 1024) in cands and (128, 128) in cands
    for bq, bk in cands:
        assert tune_search._tile_bytes(bq, bk, 64, kernel) == fa.working_set_bytes(
            kernel, bq, bk, 64, 2) <= tune_search._VMEM_BUDGET_BYTES
    # dQ holds four score-sized float32 tiles to the forward's three
    assert (tune_search._tile_bytes(512, 512, 64, "dq")
            > tune_search._tile_bytes(512, 512, 64, "fwd"))


def test_sweep_prints_the_table_row(capsys):
    """``python -m paddle_tpu.tune.search --shape ...``: each kernel timed
    alone over its candidates, fastest first, and the row for
    ``_TUNED_BLOCKS`` with the runners-up, parseable as the table's entry."""
    rc = tune_search.main(["--shape", "1,2,256,32", "--dtype", "float32",
                           "--iters", "1", "--reps", "1"])
    assert rc == 0
    lines = capsys.readouterr().out.strip().splitlines()
    rows = [json.loads(l) for l in lines[:-1]]
    assert {r["kernel"] for r in rows} == {"fwd", "dkv", "dq"}
    assert all(r["ms"] > 0 and 256 % r["block_q"] == 0 for r in rows)
    key, entry = lines[-1].split("#")[0].strip().rstrip(",").split(": ", 1)
    assert eval(key) == (256, 32, 4)
    table = eval(entry)
    assert set(table) == {"fwd", "dkv", "dq"}
    for kernel, blocks in table.items():
        best = min((r for r in rows if r["kernel"] == kernel), key=lambda r: r["ms"])
        assert blocks == (best["block_q"], best["block_k"])


def test_shape_bucket_and_variant_tag():
    assert tune_search.shape_bucket(1024) == "q1024"
    assert tune_search.shape_bucket(1000) == "q1024"
    assert tune_search.shape_bucket(8) == "q128"
    assert tune_search.shape_bucket(512, 4096) == "q512k4096"
    assert tune_search.variant_tag(True) == "causal"
    assert tune_search.variant_tag(False, window=1024) == "full_w1024"
    assert tune_search.variant_tag(True, fused_bwd=False) == "causal_xlabwd"


# ---- store: round-trip, invalidation, corruption, atomicity ----------------


def test_store_round_trip(tmp_path):
    path = str(tmp_path / "tune.json")
    st = TuneStore(path)
    key = TuneKey.render("flash_attention", "q1024", "bfloat16", "causal", "v5e")
    st.put(key, "abcd1234", {"block_q": 256, "block_k": 512},
           ms=1.25, candidates=9)
    st.save()

    st2 = TuneStore(path)
    ent = st2.get(key, fingerprint="abcd1234")
    assert ent is not None
    assert ent["config"] == {"block_q": 256, "block_k": 512}
    assert ent["ms"] == 1.25
    kernel, bucket, dtype, variant, device = TuneKey.parse(key)
    assert bucket == "q1024" and device == "v5e"


def test_store_key_rejects_separator():
    with pytest.raises(Exception):
        TuneKey.render("flash|attention", "q1024", "bf16", "causal", "cpu")


def test_fingerprint_invalidation(tune_env):
    """An entry persisted under an old kernel fingerprint must never be
    served: get() filters it, lookup counts it stale, prune drops it."""
    st = tune_autotune.get_store()
    key = TuneKey.render(
        tune_autotune.KERNEL, tune_search.shape_bucket(256), "float32",
        "causal", tune_autotune.device_kind())
    st.put(key, "0" * 16, {"block_q": 128, "block_k": 128}, ms=1.0,
           candidates=4)
    st.save()

    fp_now = tune_autotune.flash_fingerprint()
    assert fp_now != "0" * 16
    assert st.get(key, fingerprint=fp_now) is None
    assert st.is_stale(key, fp_now)

    before = prof.counters().get("tune.cache.stale", 0)
    assert tune_autotune.lookup_blocks(256, 256, dtype=jnp.float32,
                                       causal=True) is None
    assert prof.counters()["tune.cache.stale"] == before + 1

    st.prune_stale(tune_autotune.KERNEL, fp_now)
    assert st.get(key) is None


def test_kernel_fingerprint_is_stable_and_source_sensitive():
    assert kernel_fingerprint("a", "b") == kernel_fingerprint("a", "b")
    assert kernel_fingerprint("a", "b") != kernel_fingerprint("a", "c")
    assert len(tune_autotune.flash_fingerprint()) == 16


def test_corrupt_store_degrades_to_defaults(tmp_path):
    """Garbage, truncation, and CRC mismatch all mean: empty store, one
    alert runlog event, ``tune.store.corrupt_total`` bump — never a crash
    at import/serve time."""
    runlog_path = str(tmp_path / "runlog.jsonl")
    prev = set_runlog(RunLog(runlog_path))
    try:
        for i, corruption in enumerate(["not json {{{", '{"entries": 3}']):
            path = str(tmp_path / f"bad{i}.json")
            with open(path, "w") as f:
                f.write(corruption)
            before = prof.counters().get("tune.store.corrupt_total", 0)
            st = TuneStore(path)
            assert st.corrupt
            assert st.get("anything") is None
            assert prof.counters()["tune.store.corrupt_total"] == before + 1

        # a valid file whose payload was tampered with post-write
        path = str(tmp_path / "crc.json")
        good = TuneStore(path)
        good.put(TuneKey.render("k", "q128", "f32", "causal", "cpu"),
                 "f" * 16, {"block_q": 128, "block_k": 128}, ms=1.0,
                 candidates=1)
        good.save()
        blob = json.load(open(path))
        next(iter(blob["entries"].values()))["config"]["block_q"] = 999
        with open(path, "w") as f:
            json.dump(blob, f)
        st = TuneStore(path)
        assert st.corrupt and st.get("anything") is None

        alerts = [e for e in read_runlog(runlog_path)
                  if e["kind"] == "alert" and e.get("source") == "tune.store"]
        assert len(alerts) >= 3
    finally:
        set_runlog(prev)


def test_store_concurrent_writers_never_tear_the_file(tmp_path):
    """N threads, each with its own TuneStore over the same path, saving
    concurrently (the multi-process race, minus fork overhead: atomicity
    is tmp+``os.replace``, per writer). Whatever interleaving wins, the
    file on disk is always a complete, CRC-valid store."""
    path = str(tmp_path / "race.json")
    errors = []

    def writer(i):
        try:
            st = TuneStore(path)
            for j in range(5):
                st.put(TuneKey.render("k", f"q{128 * (i + 1)}", "f32",
                                      "causal", "cpu"),
                       "a" * 16, {"block_q": 128, "block_k": 128},
                       ms=float(i + j), candidates=1)
                st.save()
        except Exception as e:  # pragma: no cover - failure reporting
            errors.append(e)

    threads = [threading.Thread(target=writer, args=(i,)) for i in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    final = TuneStore(path)
    assert not final.corrupt and len(final) >= 1
    assert not [p for p in os.listdir(str(tmp_path)) if ".tmp." in p], (
        "temp files must not survive a save")


# ---- call-time resolution: store > _TUNED_BLOCKS > fitted default ----------


def test_resolve_blocks_resolution_order(tune_env):
    # 1) autotune off -> the static table answer, untouched
    pt.core.config.set_flags(autotune=False)
    assert fa.resolve_blocks(1024, 1024) == fa.tuned_blocks(1024, 1024)

    # 2) autotune on, no entry -> miss counter, falls through to the table
    pt.core.config.set_flags(autotune=True)
    tune_autotune.reset_lookup_cache()
    before = prof.counters().get("tune.cache.miss", 0)
    assert fa.resolve_blocks(1024, 1024) == fa.tuned_blocks(1024, 1024)
    assert prof.counters()["tune.cache.miss"] == before + 1

    # 3) a store winner under the live fingerprint overrides the table
    st = tune_autotune.get_store()
    key = TuneKey.render(
        tune_autotune.KERNEL, tune_search.shape_bucket(1024), "-",
        tune_search.variant_tag(False), tune_autotune.device_kind())
    st.put(key, tune_autotune.flash_fingerprint(),
           {"block_q": 512, "block_k": 256}, ms=0.5, candidates=9)
    st.save()
    tune_autotune.reset_lookup_cache()
    hit_before = prof.counters().get("tune.cache.hit", 0)
    assert fa.resolve_blocks(1024, 1024) == (512, 256)
    assert prof.counters()["tune.cache.hit"] == hit_before + 1
    # memoized: a second resolve costs no extra counter bump
    assert fa.resolve_blocks(1024, 1024) == (512, 256)
    assert prof.counters()["tune.cache.hit"] == hit_before + 1

    # 4) stored blocks that don't divide the exact lengths are refused
    # (bucket neighbor: 1000 shares q1024 but 512 doesn't divide it)
    assert fa.resolve_blocks(1000, 1000) == fa.tuned_blocks(1000, 1000)


def test_autotune_end_to_end_on_cpu(tune_env, rng):
    """Full loop: sweep -> persist winner -> flash_attention picks it up
    through the public entry point."""
    res = tune_autotune.autotune_flash_attention(
        shapes=((1, 2, 256, 64),), causal=True, dtype=jnp.float32,
        include_bwd=False, iters=1, warmup=0)
    ((key, info),) = res.items()
    assert not info["partial"] and "best" in info
    assert info["speedup_vs_default"] > 0

    tuned = tune_autotune.lookup_blocks(256, 256, dtype=jnp.float32,
                                        causal=True)
    assert tuned == (info["best"]["block_q"], info["best"]["block_k"])

    q = jnp.asarray(rng.randn(1, 2, 256, 64).astype(np.float32))
    out = fa.flash_attention(q, q, q, causal=True, interpret=True)
    ref = fa._reference_attention(q, q, q, True, 64 ** -0.5)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_autotune_partial_sweep_never_persists(tune_env):
    calls = [0]

    def stopper():
        calls[0] += 1
        return calls[0] > 1

    res = tune_autotune.autotune_flash_attention(
        shapes=((1, 2, 512, 64),), causal=False, dtype=jnp.float32,
        include_bwd=False, iters=1, warmup=0, should_stop=stopper)
    ((key, info),) = res.items()
    assert info["partial"]
    assert tune_autotune.get_store().get(key) is None


# ---- warmup manifest -------------------------------------------------------


def test_warmup_manifest_round_trip_and_dedup(tune_env):
    assert tune_warmup.record_compile("m1", "serving", sig=[[5]], bucket=4)
    assert not tune_warmup.record_compile("m1", "serving", sig=[[5]], bucket=4)
    assert tune_warmup.record_compile("m1", "serving", sig=[[5]], bucket=8)

    tune_warmup.reset_manifests()  # fresh process: read back from disk
    man = tune_warmup.get_manifest("m1")
    ents = man.entries("serving")
    assert [e["bucket"] for e in ents] == [4, 8]
    assert all(e["kind"] == "serving" for e in ents)


def test_warmup_manifest_corrupt_falls_back_empty(tune_env):
    path = tune_warmup.manifest_path("broken")
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        f.write('{"entries": [1, 2')
    before = prof.counters().get("tune.warmup.corrupt_total", 0)
    man = tune_warmup.WarmupManifest("broken", path=path)
    assert man.corrupt and man.entries() == []
    assert prof.counters()["tune.warmup.corrupt_total"] == before + 1
    # and recording over the corpse works
    assert man.record("serving", sig=[[3]], bucket=2)
    man.save()
    assert not tune_warmup.WarmupManifest("broken", path=path).corrupt


def test_record_compile_noop_without_manifest_dir():
    pt.core.config.set_flags(tune_cache_dir="")
    tune_warmup.reset_manifests()
    if pt.core.config.flags().compilation_cache_dir:
        pytest.skip("compilation cache dir configured; manifest dir exists")
    assert tune_warmup.manifest_dir() is None
    assert tune_warmup.record_compile("m", "executor", target="t") is False


def test_tree_signature_shapes_and_scalars():
    sig = tune_warmup.tree_signature(
        ((jnp.zeros((2, 3), jnp.float32),), {"n": 7}))
    assert [[2, 3], "float32"] in sig
    assert ["py", "int"] in sig


# ---- prewarm: compile-once invariants across restart -----------------------


def _lm_spec():
    spec = pt.models.get_model("transformer_lm", seq_len=64, vocab=97,
                               d_model=32, d_inner=64, num_heads=4,
                               n_layers=2)
    rng = np.random.RandomState(1)
    variables = spec.model.init(0, *spec.synth_batch(2, rng))
    return spec, variables


def test_decode_prewarm_compile_once(tune_env):
    """PR 9's acceptance invariant survives the restart path: an engine
    started from the warmup manifest (warmup=False, prewarm) has
    ``decode_step_cache_size() == 1`` before AND after live traffic."""
    from paddle_tpu.serving import DecodeConfig, DecodeEngine

    spec, variables = _lm_spec()
    cfg = spec.extra["cfg"]
    dconf = dict(max_slots=2, page_size=16, max_context=48, prefill_chunk=16,
                 num_pages=8)

    eng = DecodeEngine(variables, cfg, decode=DecodeConfig(**dconf))
    eng.close()  # warmup recorded + saved the manifest

    before = prof.counters().get("tune.prewarm.replayed_total", 0)
    eng2 = DecodeEngine(variables, cfg, decode=DecodeConfig(
        warmup=False, prewarm=True, **dconf))
    try:
        assert prof.counters().get("tune.prewarm.replayed_total", 0) > before
        assert eng2.decode_step_cache_size() == 1
        prompt = np.arange(1, 7, dtype=np.int32)
        out = eng2.submit(prompt, 8).result(timeout=120)
        assert len(out.tokens) == 8
        assert eng2.decode_step_cache_size() == 1, (
            "traffic after prewarm must not compile a second step")
    finally:
        eng2.close()


def test_serving_prewarm_no_compiles_under_traffic(tune_env, rng):
    """Serving restart from the manifest: prewarm compiles every recorded
    (signature, bucket), then real traffic adds zero AOT entries."""
    from paddle_tpu.reader.feeder import FeedSpec
    from paddle_tpu.serving import ServingConfig, ServingEngine

    def _net(x):
        return pt.layers.fc(x, size=3, name="fc_pw")

    model = pt.build(_net)
    x0 = rng.randn(4, 5).astype(np.float32)
    variables = model.init(0, x0)
    specs = [FeedSpec("x", (5,), "float32")]
    sconf = dict(max_batch_size=4, max_queue_delay_s=0.005, num_replicas=1,
                 lint_model=False)

    eng = ServingEngine(model, variables, specs,
                        config=ServingConfig(**sconf))
    warm_sizes = eng.aot_cache_sizes()
    eng.close()

    eng2 = ServingEngine(model, variables, specs, config=ServingConfig(
        warmup=False, prewarm=True, **sconf))
    try:
        assert eng2.aot_cache_sizes() == warm_sizes
        out = eng2.infer({"x": rng.randn(2, 5).astype(np.float32)})
        assert np.asarray(out).shape == (2, 3)
        assert eng2.aot_cache_sizes() == warm_sizes, (
            "traffic after prewarm must not add AOT entries")
    finally:
        eng2.close()


def test_prewarm_without_manifest_is_harmless(tune_env):
    from paddle_tpu.serving import DecodeConfig, DecodeEngine

    spec, variables = _lm_spec()
    eng = DecodeEngine(variables, spec.extra["cfg"], decode=DecodeConfig(
        warmup=False, prewarm=True, max_slots=2, page_size=16,
        max_context=48, prefill_chunk=16, num_pages=8))
    try:
        # nothing recorded for this geometry yet: prewarm is a no-op and
        # lazy first-traffic compilation still works
        assert eng.prewarm() == 0
        out = eng.submit(np.arange(1, 5, dtype=np.int32), 6).result(
            timeout=120)
        assert len(out.tokens) == 6
    finally:
        eng.close()
