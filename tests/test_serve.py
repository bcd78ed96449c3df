"""Serving engine tests: packed-checkpoint bit-exactness, batched-decode
parity vs the single-request serve path, chunked-prefill bit-identity,
scheduler invariants + fuzz."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.config import ModelConfig
from repro.models.model import (
    decode_step, init_caches, init_params, prefill_chunk,
)
from repro.models.quant import PackedWeight
from repro.serve import (
    ServeEngine, SlotScheduler, load_packed_checkpoint, prequantize_params,
    save_packed_checkpoint, tree_nbytes,
)

KEY = jax.random.PRNGKey(0)


def _cfg(**kw):
    base = dict(name="serve-test", family="dense", n_layers=2, d_model=64,
                n_heads=2, n_kv_heads=1, d_ff=128, vocab_size=97,
                remat=False, quant="serve")
    base.update(kw)
    return ModelConfig(**base)


@pytest.fixture(scope="module")
def packed_model():
    cfg = _cfg()
    params = init_params(KEY, cfg)
    return cfg, params, prequantize_params(params, cfg)


# ---------------------------------------------------------------------------
# Prequantization / packed checkpoints
# ---------------------------------------------------------------------------

@pytest.mark.smoke
def test_packed_checkpoint_roundtrip_bitexact(packed_model, tmp_path):
    """Packed u8 streams (and residual bf16 leaves) survive save/load
    bit-for-bit — the serving engine never re-quantizes."""
    cfg, _, packed = packed_model
    save_packed_checkpoint(str(tmp_path), packed, cfg)
    packed2, extra = load_packed_checkpoint(str(tmp_path), cfg)
    assert extra["format"] == "mx-packed"
    assert extra["codec"] == "m2xfp"
    flat1 = jax.tree_util.tree_leaves(packed)
    flat2 = jax.tree_util.tree_leaves(packed2)
    assert len(flat1) == len(flat2)
    for a, b in zip(flat1, flat2):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.smoke
def test_packed_tree_is_4p5_bits_on_gemm_weights(packed_model):
    cfg, params, packed = packed_model
    for node in jax.tree.leaves(
            packed, is_leaf=lambda x: isinstance(x, PackedWeight)):
        if isinstance(node, PackedWeight):
            n_elems = 2 * node.codes.size
            assert 8 * tree_nbytes(node) / n_elems == 4.5
    # and the packed tree is strictly smaller than the dense one
    assert tree_nbytes(packed) < tree_nbytes(params)


@pytest.mark.parametrize("arch", ["qwen2.5-14b", "qwen3-8b"])
def test_init_packed_params_matches_two_step_path(arch):
    """The per-layer seeded builder gives, leaf for leaf and bit for bit,
    the packed tree of init_params followed by prequantize_params."""
    from repro.configs.registry import smoke_config
    from repro.serve import init_packed_params
    cfg = smoke_config(arch)
    want = prequantize_params(init_params(KEY, cfg), cfg)
    got = init_packed_params(KEY, cfg)
    flat_w, tree_w = jax.tree_util.tree_flatten_with_path(want)
    flat_g, tree_g = jax.tree_util.tree_flatten_with_path(got)
    assert tree_g == tree_w
    for (path, a), (_, b) in zip(flat_w, flat_g):
        assert a.dtype == b.dtype, jax.tree_util.keystr(path)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=jax.tree_util.keystr(path))


def _load_chip_smoke():
    import importlib.util
    import pathlib
    path = pathlib.Path(__file__).resolve().parents[1] / "chip_smoke.py"
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_chip_smoke_refuses_cpu(capsys):
    """The smoke run has no CPU path: on a CPU backend it exits non-zero
    before any phase and prints no result line."""
    assert jax.devices()[0].platform == "cpu"
    smoke = _load_chip_smoke()
    with pytest.raises(SystemExit) as exc:
        smoke.require_tpu()
    assert exc.value.code not in (0, None)
    with pytest.raises(SystemExit) as exc:
        smoke.main([])
    assert exc.value.code not in (0, None)
    assert '"ok"' not in capsys.readouterr().out


def test_compile_cache_dir(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR, where set, is left to JAX; otherwise the
    cache goes to the same fixed directory inside the checkout."""
    import pathlib
    from jax.experimental.compilation_cache import compilation_cache
    from repro.launch.compile_cache import CACHE_DIR, use_compile_cache
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        assert use_compile_cache() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert use_compile_cache() == use_compile_cache() == str(CACHE_DIR)
        assert jax.config.jax_compilation_cache_dir == str(CACHE_DIR)
        repo = pathlib.Path(__file__).resolve().parents[1]
        assert CACHE_DIR == repo / ".jax_cache"
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
        compilation_cache.reset_cache()


def test_load_rejects_dense_checkpoint(packed_model, tmp_path):
    cfg, params, _ = packed_model
    from repro.checkpoint import save_state
    save_state(str(tmp_path), 0, params)
    with pytest.raises(ValueError, match="not a packed"):
        load_packed_checkpoint(str(tmp_path), cfg)


def test_load_rejects_codec_mismatch(packed_model, tmp_path):
    """A checkpoint packed as m2xfp must not restore under a config that
    expects different streams — the error names both codecs."""
    cfg, _, packed = packed_model
    save_packed_checkpoint(str(tmp_path), packed, cfg)
    other = dataclasses.replace(cfg, quant_format="mxfp4")
    with pytest.raises(ValueError, match="codec 'm2xfp'.*'mxfp4'"):
        load_packed_checkpoint(str(tmp_path), other)


def test_load_rejects_manifest_without_codec(packed_model, tmp_path):
    """A v2 manifest that lost its codec field fails actionably instead of
    guessing."""
    cfg, _, packed = packed_model
    from repro.checkpoint import save_state
    save_state(str(tmp_path), 0, packed,
               extra={"format": "mx-packed", "format_version": 2})
    with pytest.raises(ValueError, match="records no codec"):
        load_packed_checkpoint(str(tmp_path), cfg)


@pytest.mark.parametrize("fmt", ["mxfp4", "nvfp4"])
def test_engine_serves_packed_checkpoint_any_codec(packed_model, tmp_path,
                                                   fmt):
    """End-to-end per codec: prequantize -> save -> load -> generate. The
    engine never sees a dense weight and the loaded tree is codec-tagged."""
    cfg, params, _ = packed_model
    fcfg = dataclasses.replace(cfg, quant_format=fmt)
    save_packed_checkpoint(str(tmp_path), prequantize_params(params, fcfg),
                           fcfg)
    packed, extra = load_packed_checkpoint(str(tmp_path), fcfg)
    assert extra["codec"] == fmt
    leaves = [l for l in jax.tree.leaves(
        packed, is_leaf=lambda x: isinstance(x, PackedWeight))
        if isinstance(l, PackedWeight)]
    assert leaves and all(l.codec == fmt for l in leaves)
    eng = ServeEngine(packed, fcfg, n_slots=1, max_len=16)
    out = eng.generate([[5, 6, 7]], max_new_tokens=2)
    assert len(out[0]) == 2 and all(0 <= t < cfg.vocab_size for t in out[0])


# ---------------------------------------------------------------------------
# Golden tokens: the m2xfp serve path is pinned bit-exactly
# ---------------------------------------------------------------------------

_GOLDEN_PROMPTS = [[94, 94, 95, 36, 16],
                   [89, 10, 25, 13, 30, 51, 11, 77, 23],
                   [76, 30, 76]]
# captured from the pre-codec-registry serve path (PRNGKey(0) params,
# n_slots=2, max_len=32, prefill_chunk=4, greedy, 6 new tokens) — any
# change to these tokens is a numerics regression in the packed m2xfp
# pipeline, not a refactor
_GOLDEN_M2XFP = [[90, 70, 70, 86, 68, 68],
                 [45, 96, 34, 11, 96, 64],
                 [41, 41, 30, 93, 41, 41]]
_GOLDEN_M2XFP_KVQ = [[90, 6, 38, 86, 6, 29],
                     [45, 96, 64, 64, 75, 3],
                     [30, 5, 64, 39, 39, 5]]


@pytest.mark.smoke
def test_golden_tokens_m2xfp(packed_model):
    cfg, _, packed = packed_model
    eng = ServeEngine(packed, cfg, n_slots=2, max_len=32, prefill_chunk=4)
    assert eng.generate(_GOLDEN_PROMPTS, max_new_tokens=6) == _GOLDEN_M2XFP


def test_golden_tokens_m2xfp_quantized_kv(packed_model):
    cfg, params, _ = packed_model
    qcfg = dataclasses.replace(cfg, kv_quant="m2xfp")
    packed = prequantize_params(params, qcfg)
    eng = ServeEngine(packed, qcfg, n_slots=2, max_len=32, prefill_chunk=4)
    assert eng.generate(_GOLDEN_PROMPTS,
                        max_new_tokens=6) == _GOLDEN_M2XFP_KVQ


# ---------------------------------------------------------------------------
# Batched decode parity
# ---------------------------------------------------------------------------

def _serve_single(packed, cfg, prompt, n_new, max_len=32):
    """Reference: one request alone through the scalar-index serve path."""
    caches = init_caches(cfg, 1, max_len)
    step = jax.jit(lambda p, b, c, i: decode_step(p, cfg, b, c, i))
    tok = jnp.asarray([[prompt[0]]], jnp.int32)
    out, t = [], 0
    while len(out) < n_new:
        lg, caches = step(packed, {"tokens": tok}, caches, jnp.int32(t))
        t += 1
        if t < len(prompt):
            tok = jnp.asarray([[prompt[t]]], jnp.int32)
        else:
            nxt = int(jnp.argmax(lg[0, -1]))
            out.append(nxt)
            tok = jnp.asarray([[nxt]], jnp.int32)
    return out


@pytest.mark.smoke
def test_batched_decode_matches_single_request(packed_model):
    """Continuous batching with ragged prompt lengths + slot reuse produces
    exactly the tokens of each request served alone."""
    cfg, _, packed = packed_model
    rng = np.random.default_rng(3)
    prompts = [list(map(int, rng.integers(0, cfg.vocab_size, n)))
               for n in (5, 3, 7, 2)]
    eng = ServeEngine(packed, cfg, n_slots=2, max_len=32)
    outs = eng.generate(prompts, max_new_tokens=4)
    eng.scheduler.check()
    for prompt, got in zip(prompts, outs):
        assert got == _serve_single(packed, cfg, prompt, 4)


def test_batched_decode_parity_with_quantized_kv(packed_model):
    """Same parity holds when KV pages are packed Sg-EM streams."""
    cfg, params, _ = packed_model
    qcfg = dataclasses.replace(cfg, kv_quant="m2xfp")
    packed = prequantize_params(params, qcfg)
    rng = np.random.default_rng(4)
    prompts = [list(map(int, rng.integers(0, qcfg.vocab_size, n)))
               for n in (4, 6, 3)]
    eng = ServeEngine(packed, qcfg, n_slots=2, max_len=32)
    outs = eng.generate(prompts, max_new_tokens=3)
    for prompt, got in zip(prompts, outs):
        assert got == _serve_single(packed, qcfg, prompt, 3)


def test_slot_reuse_does_not_leak_state(packed_model):
    """A request admitted into a reused slot sees a clean page: serving the
    same prompt twice (before/after other traffic) yields identical
    output."""
    cfg, _, packed = packed_model
    rng = np.random.default_rng(5)
    probe = list(map(int, rng.integers(0, cfg.vocab_size, 5)))
    filler = [list(map(int, rng.integers(0, cfg.vocab_size, 6)))
              for _ in range(3)]
    eng = ServeEngine(packed, cfg, n_slots=2, max_len=32)
    first = eng.generate([probe] + filler, max_new_tokens=4)[0]
    again = eng.generate([probe], max_new_tokens=4)[0]
    assert first == again


# ---------------------------------------------------------------------------
# Chunked prefill: bit-identity with the one-token path
# ---------------------------------------------------------------------------

def _assert_trees_equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        assert x.dtype == y.dtype and x.shape == y.shape
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.mark.parametrize("chunk", [1, 3, 8])
@pytest.mark.parametrize("kw", [
    {}, {"kv_quant": "m2xfp"}, {"sliding_window": 4},
], ids=["dense", "kvq", "slide"])
def test_prefill_chunk_bitexact_caches_and_logits(packed_model, chunk, kw):
    """``prefill_chunk`` over T tokens leaves caches AND logits bit-equal
    to T sequential ``decode_step`` calls — including packed Sg-EM KV pages
    and a sliding window narrower than the chunk (ring overwrite order)."""
    cfg, params, _ = packed_model
    qcfg = dataclasses.replace(cfg, **kw)
    packed = prequantize_params(params, qcfg)
    rng = np.random.default_rng(17)
    b, p_len, w = 2, 7, 16
    toks = rng.integers(0, qcfg.vocab_size, (b, p_len)).astype(np.int32)

    # reference: one token at a time through decode_step
    ref_caches = init_caches(qcfg, b, w, per_slot=True)
    ref_logits = None
    for t in range(p_len):
        ref_logits, ref_caches = decode_step(
            packed, qcfg, {"tokens": jnp.asarray(toks[:, t:t + 1])},
            ref_caches, jnp.full((b,), t, jnp.int32))

    # chunked: same tokens in chunks of `chunk`
    caches = init_caches(qcfg, b, w, per_slot=True)
    logits, last_c = None, 0
    for start in range(0, p_len, chunk):
        last_c = min(chunk, p_len - start)
        block = np.zeros((b, chunk), np.int32)
        block[:, :last_c] = toks[:, start:start + last_c]
        logits, caches = prefill_chunk(
            packed, qcfg, {"tokens": jnp.asarray(block)}, caches,
            jnp.full((b,), start, jnp.int32),
            jnp.full((b,), last_c, jnp.int32))
    _assert_trees_equal(caches, ref_caches)
    np.testing.assert_array_equal(np.asarray(logits[:, last_c - 1]),
                                  np.asarray(ref_logits[:, -1]))


def test_prefill_chunk_ragged_lengths(packed_model):
    """One launch, per-slot lengths {1, 3, 8, 0}: every live slot's cache
    rows and last-position logits match a batch that fed exactly that many
    tokens sequentially; the length-0 slot's rows stay bit-equal to init
    (no masked write leaks)."""
    cfg, _, packed = packed_model
    rng = np.random.default_rng(23)
    b, t_max, w = 4, 8, 16
    lens = np.array([1, 3, 8, 0], np.int32)
    toks = rng.integers(0, cfg.vocab_size, (b, t_max)).astype(np.int32)

    caches = init_caches(cfg, b, w, per_slot=True)
    logits, caches = prefill_chunk(
        packed, cfg, {"tokens": jnp.asarray(toks)}, caches,
        jnp.zeros((b,), jnp.int32), jnp.asarray(lens))
    lg = np.asarray(logits, np.float32)

    ref_caches = init_caches(cfg, b, w, per_slot=True)
    for t in range(t_max):
        ref_lg, ref_caches = decode_step(
            packed, cfg, {"tokens": jnp.asarray(toks[:, t:t + 1])},
            ref_caches, jnp.full((b,), t, jnp.int32))
        # rows whose chunk ends here: logits and cache rows must match now
        for row in np.flatnonzero(lens == t + 1):
            np.testing.assert_array_equal(
                lg[row, t], np.asarray(ref_lg[:, -1], np.float32)[row])
            for leaf, ref in zip(jax.tree.leaves(caches),
                                 jax.tree.leaves(ref_caches)):
                np.testing.assert_array_equal(np.asarray(leaf[:, row]),
                                              np.asarray(ref[:, row]))
    # length-0 slot: bit-identical to init
    init = init_caches(cfg, b, w, per_slot=True)
    for leaf, ref in zip(jax.tree.leaves(caches), jax.tree.leaves(init)):
        np.testing.assert_array_equal(np.asarray(leaf[:, 3]),
                                      np.asarray(ref[:, 3]))


@pytest.mark.smoke
@pytest.mark.parametrize("chunk", [3, 8])
def test_chunked_engine_matches_one_token_engine(packed_model, chunk):
    """Engine end-to-end: chunked prefill generates exactly the tokens of
    the legacy one-token path (same traffic, same slots)."""
    cfg, _, packed = packed_model
    rng = np.random.default_rng(29)
    prompts = [list(map(int, rng.integers(0, cfg.vocab_size, n)))
               for n in (1, 3, 8, 12, 5)]
    legacy = ServeEngine(packed, cfg, n_slots=2, max_len=32, prefill_chunk=1)
    chunked = ServeEngine(packed, cfg, n_slots=2, max_len=32,
                          prefill_chunk=chunk)
    ref = legacy.generate(prompts, max_new_tokens=4)
    got = chunked.generate(prompts, max_new_tokens=4)
    assert got == ref
    chunked.scheduler.check()
    assert chunked.stats.steps < legacy.stats.steps


def test_chunked_engine_parity_with_quantized_kv_and_window(packed_model):
    cfg, params, _ = packed_model
    qcfg = dataclasses.replace(cfg, kv_quant="m2xfp", sliding_window=6)
    packed = prequantize_params(params, qcfg)
    rng = np.random.default_rng(31)
    prompts = [list(map(int, rng.integers(0, qcfg.vocab_size, n)))
               for n in (9, 2, 7)]
    eng = ServeEngine(packed, qcfg, n_slots=2, max_len=16, prefill_chunk=8)
    outs = eng.generate(prompts, max_new_tokens=3)
    for prompt, got in zip(prompts, outs):
        assert got == _serve_single(packed, qcfg, prompt, 3, max_len=16)


def test_prefill_budget_never_starves_decode_or_oldest(packed_model):
    """With a tiny token budget the engine still finishes everything, and
    bit-identically: decode slots always advance, the oldest prefilling
    request always gets at least one token."""
    cfg, _, packed = packed_model
    rng = np.random.default_rng(37)
    prompts = [list(map(int, rng.integers(0, cfg.vocab_size, n)))
               for n in (12, 12, 12)]
    ref = ServeEngine(packed, cfg, n_slots=2, max_len=32,
                      prefill_chunk=1).generate(prompts, max_new_tokens=3)
    eng = ServeEngine(packed, cfg, n_slots=2, max_len=32,
                      prefill_chunk=8, prefill_budget=3)
    assert eng.generate(prompts, max_new_tokens=3) == ref
    eng.scheduler.check()


def test_steps_to_first_token_4x_for_128_prompt(packed_model):
    """Acceptance: a 128-token prompt reaches its first sampled token in
    >= 4x fewer engine steps with chunked prefill, identical tokens."""
    cfg, _, packed = packed_model
    rng = np.random.default_rng(41)
    prompt = list(map(int, rng.integers(0, cfg.vocab_size, 128)))
    legacy = ServeEngine(packed, cfg, n_slots=2, max_len=160,
                         prefill_chunk=1)
    chunked = ServeEngine(packed, cfg, n_slots=2, max_len=160,
                          prefill_chunk=8)
    ref = legacy.generate([prompt], max_new_tokens=2)
    got = chunked.generate([prompt], max_new_tokens=2)
    assert got == ref
    ttft_1, ttft_c = legacy.mean_ttft_steps(), chunked.mean_ttft_steps()
    assert ttft_1 == 128 and ttft_c == 16
    assert ttft_1 / ttft_c >= 4.0


def test_recurrent_families_force_one_token_prefill(packed_model):
    cfg, _, _ = packed_model
    scfg = dataclasses.replace(cfg, family="ssm", quant="none",
                               ssm_state=16, ssm_head_dim=16)
    params = init_params(KEY, scfg)
    eng = ServeEngine(params, scfg, n_slots=1, max_len=32, prefill_chunk=8)
    assert eng.chunk == 1
    out = eng.generate([[1, 2, 3, 4]], max_new_tokens=2)
    assert len(out[0]) == 2


# ---------------------------------------------------------------------------
# Scheduler invariants
# ---------------------------------------------------------------------------

@pytest.mark.smoke
def test_scheduler_admit_evict_invariants():
    sched = SlotScheduler(3)
    reqs = [sched.submit([1, 2], max_new_tokens=4) for _ in range(5)]
    sched.check()
    admitted = sched.admit(step=0)
    assert [r.rid for r in admitted] == [0, 1, 2]      # FIFO
    assert not sched.free and len(sched.queue) == 2
    sched.check()
    # evicting frees the slot; next admit reuses it for the oldest queued
    slot = reqs[1].slot
    sched.evict(slot, step=7)
    sched.check()
    assert reqs[1].state == "finished" and reqs[1].finish_step == 7
    nxt = sched.admit(step=8)
    assert [r.rid for r in nxt] == [3] and nxt[0].slot == slot
    sched.check()
    # draining everything returns all slots to free
    while sched.has_work:
        for s in list(sched.active):
            sched.evict(s)
        sched.admit()
        sched.check()
    assert sorted(sched.free) == [0, 1, 2]
    assert len(sched.finished) == 5


def test_scheduler_rejects_bad_requests():
    sched = SlotScheduler(1)
    with pytest.raises(ValueError):
        sched.submit([], max_new_tokens=2)
    with pytest.raises(ValueError):
        SlotScheduler(0)


def test_engine_rejects_over_capacity_prompt(packed_model):
    cfg, _, packed = packed_model
    eng = ServeEngine(packed, cfg, n_slots=1, max_len=8)
    with pytest.raises(ValueError, match="capacity"):
        eng.submit(list(range(6)), max_new_tokens=6)


def test_eos_stops_generation(packed_model):
    """A request whose sampler emits eos finishes early and frees the
    slot."""
    cfg, _, packed = packed_model

    def always_eos(logits):
        return np.full((logits.shape[0],), 42, np.int32)

    eng = ServeEngine(packed, cfg, n_slots=1, max_len=32,
                      sample_fn=always_eos)
    req = eng.submit([1, 2, 3], max_new_tokens=10, eos_id=42)
    eng.run()
    assert req.output == [42] and req.state == "finished"
    eng.scheduler.check()


# ---------------------------------------------------------------------------
# Stats / accounting
# ---------------------------------------------------------------------------

def test_run_returns_only_this_drain(packed_model):
    """A second submit/run cycle must not re-deliver earlier requests."""
    cfg, _, packed = packed_model
    eng = ServeEngine(packed, cfg, n_slots=2, max_len=32)
    r1 = eng.submit([1, 2, 3], max_new_tokens=2)
    first = eng.run()
    assert [r.rid for r in first] == [r1.rid]
    r2 = eng.submit([4, 5], max_new_tokens=2)
    second = eng.run()
    assert [r.rid for r in second] == [r2.rid]


@pytest.mark.parametrize("chunk", [1, 4])
def test_stats_token_accounting(packed_model, chunk):
    """Per request: prompt feeds len(prompt)-1 prefill tokens (the last
    prompt token's step samples) and every output token counts as
    generated — independent of how prefill is chunked."""
    cfg, _, packed = packed_model
    eng = ServeEngine(packed, cfg, n_slots=2, max_len=32,
                      prefill_chunk=chunk)
    prompts = [[1, 2, 3, 4], [5, 6]]
    eng.generate(prompts, max_new_tokens=3)
    s = eng.stats
    assert s.generated_tokens == 2 * 3
    assert s.prefill_tokens == sum(len(p) - 1 for p in prompts)
    assert s.steps == s.prefill_steps + s.decode_steps
    # a slot-step consumes >= 1 token; with chunk=1, exactly one
    assert s.slot_steps <= s.prefill_tokens + s.generated_tokens
    if chunk == 1:
        assert s.prefill_tokens + s.generated_tokens == s.slot_steps
        assert s.prefill_steps == 0
    else:
        assert s.prefill_steps > 0
    assert 0 < s.occupancy <= 1


# ---------------------------------------------------------------------------
# Fuzz: randomized traffic against the scheduler and the engine
# ---------------------------------------------------------------------------

@pytest.mark.fuzz
@pytest.mark.parametrize("seed", range(8))
def test_scheduler_fuzz_invariants(seed):
    """Randomized submit/plan/consume/evict traffic. After every operation:
    slots partition free/active, no slot serves two requests, consumed
    never overruns the prompt, occupancy <= 1; at drain every request
    finished with a full output."""
    rng = np.random.default_rng(seed)
    sched = SlotScheduler(int(rng.integers(1, 5)))
    submitted, step = [], 0
    n_to_submit = int(rng.integers(5, 25))
    while len(submitted) < n_to_submit or sched.has_work:
        step += 1
        if len(submitted) < n_to_submit and rng.random() < 0.5:
            n_new = int(rng.integers(1, 4))
            for _ in range(n_new):
                req = sched.submit(
                    list(map(int, rng.integers(0, 97,
                                               int(rng.integers(1, 12))))),
                    max_new_tokens=int(rng.integers(1, 5)))
                submitted.append(req)
            sched.check()
        sched.admit(step)
        sched.check()
        assert sched.occupancy <= 1
        rids = [r.rid for r in sched.active.values()]
        assert len(rids) == len(set(rids)), "slot serves two requests"
        if not sched.active:
            continue
        budget = (None if rng.random() < 0.5
                  else int(rng.integers(1, 9)))
        plan = sched.plan_chunks(int(rng.integers(1, 9)), budget)
        assert set(plan) == set(sched.active)
        # decode slots always progress; so does the oldest prefilling one
        prefilling = sorted(
            (r for r in sched.active.values() if r.phase == "prefill"),
            key=lambda r: (r.admit_step, r.rid))
        for slot, req in sched.active.items():
            if req.phase == "decode":
                assert plan[slot] == 1
            else:
                assert 0 <= plan[slot] <= len(req.prompt) - req.consumed
        if prefilling:
            assert plan[prefilling[0].slot] >= 1
        # consume the plan the way the engine does
        for slot, req in list(sched.active.items()):
            c = plan[slot]
            if c == 0:
                continue
            if req.phase == "prefill":
                req.consumed += c
                if req.consumed < len(req.prompt):
                    continue
            req.output.append(int(rng.integers(0, 97)))
            if req.done:
                sched.evict(slot, step)
        sched.check()
    assert len(sched.finished) == len(submitted)
    for req in submitted:
        assert req.state == "finished"
        assert req.consumed == len(req.prompt)
        assert len(req.output) == req.max_new_tokens


@pytest.mark.fuzz
@pytest.mark.parametrize("seed", [0, 1])
def test_engine_fuzz_matches_single_request(packed_model, seed):
    """Randomized prompt lengths / chunk / budget / slot churn: every
    request's tokens equal serving it alone, and reused slots leak no KV
    state into later requests."""
    cfg, _, packed = packed_model
    rng = np.random.default_rng(100 + seed)
    prompts = [list(map(int, rng.integers(0, cfg.vocab_size,
                                          int(rng.integers(1, 14)))))
               for _ in range(6)]
    eng = ServeEngine(
        packed, cfg, n_slots=int(rng.integers(1, 4)), max_len=32,
        prefill_chunk=int(rng.integers(2, 9)),
        prefill_budget=(None if rng.random() < 0.5
                        else int(rng.integers(1, 10))))
    outs = eng.generate(prompts, max_new_tokens=3)
    eng.scheduler.check()
    for prompt, got in zip(prompts, outs):
        assert got == _serve_single(packed, cfg, prompt, 3)
