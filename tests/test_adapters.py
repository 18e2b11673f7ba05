"""Multi-LoRA multiplexing (serving/adapters.py + the gathered grouped
adapter matmul in models/transformer.py) — ISSUE 10's adapter half.

The acceptance invariants:
- a mixed batch of base + ≥2 adapters decodes in ONE program
  (compiled_programs flat across the mix, same contract as the paged pool);
- every slot's greedy output is token-exact vs a single-tenant run of the
  SAME engine config (batch composition must never change outputs);
- residency is an LRU cache over a fixed pool: registration is unbounded,
  rows are not, swaps are counted, pinned rows never evicted;
- the `adapter` fault site (host corruption of the dispatch-facing row)
  quarantines ONLY the victim, survivors token-exact.

Engine-pair-heavy tests are `slow` (tier-1 runs under a hard timeout; the
chaos CI step runs them with LSTPU_FAULT_SEED pinned).
"""

import dataclasses

import jax
import numpy as np
import pytest

from langstream_tpu.models.configs import MODEL_PRESETS, GenerationOptions
from langstream_tpu.models.transformer import init_params
from langstream_tpu.serving.adapters import (
    AdapterPoolExhausted,
    AdapterRegistry,
    AdapterSpec,
    init_random_lora,
    lora_pool_bytes,
    rows_for_fraction,
)
from langstream_tpu.serving.engine import GenerationRequest, ServingEngine
from langstream_tpu.serving.faultinject import FaultInjector

CFG = dataclasses.replace(MODEL_PRESETS["tiny-test"], dtype="float32")
PARAMS = init_params(CFG, jax.random.PRNGKey(0))

ADAPTERS = [
    {"name": "tenant-a", "rank": 4, "scale": 2.0, "seed": 11},
    {"name": "tenant-b", "rank": 4, "scale": 2.0, "seed": 22},
]
PROMPT = [72, 101, 108, 108, 111, 32, 119, 111]
GREEDY = GenerationOptions(max_new_tokens=12, temperature=0.0)


def make_engine(**kw):
    kw.setdefault("max_batch", 4)
    kw.setdefault("max_seq_len", 128)
    kw.setdefault("decode_chunk", 4)
    kw.setdefault("adapters", ADAPTERS)
    kw.setdefault("constrained_decoding", "off")
    engine = ServingEngine(CFG, PARAMS, **kw)
    engine.start()
    return engine


# ---------------------------------------------------------------------------
# registry units (tier-1: pure host + one tiny device pool)
# ---------------------------------------------------------------------------


def test_registry_acquire_release_refcounts_and_lru():
    reg = AdapterRegistry(CFG, rows=3, rank=4)  # base + 2 usable rows
    for i, name in enumerate(("a", "b", "c")):
        reg.register(AdapterSpec(name=name, rank=4, seed=i))
    ra = reg.acquire("a")
    rb = reg.acquire("b")
    assert ra != rb and ra > 0 and rb > 0
    assert reg.resident == 2 and reg.swaps_total == 2
    # pool full and both pinned: third adapter cannot swap in
    with pytest.raises(AdapterPoolExhausted):
        reg.acquire("c")
    # releasing "a" makes it the LRU victim; "c" takes its row
    reg.release("a")
    rc = reg.acquire("c")
    assert rc == ra and reg.swaps_total == 3
    # "a" swaps back in once "b" frees (LRU over unpinned rows only)
    reg.release("b")
    ra2 = reg.acquire("a")
    assert ra2 == rb and reg.swaps_total == 4
    assert set(reg.advertised()) == {"a", "c"}


def test_registry_rejects_unknown_and_oversized():
    reg = AdapterRegistry(CFG, rows=2, rank=4)
    with pytest.raises(KeyError):
        reg.acquire("nope")
    with pytest.raises(ValueError):
        reg.register(AdapterSpec(name="big", rank=8))  # > pool rank


def test_registry_rank_padding_zero_extends():
    reg = AdapterRegistry(CFG, rows=2, rank=8)
    reg.register(AdapterSpec(name="small", rank=4, seed=3))
    state = reg._by_name["small"]
    a = state.host["wq"]["a"]
    assert a.shape[-1] == 8
    assert np.all(a[..., 4:] == 0)  # padded columns contribute nothing


def test_pool_bytes_and_rows_for_fraction_arithmetic():
    per_row = lora_pool_bytes(CFG, 1, 8)
    assert per_row > 0
    assert lora_pool_bytes(CFG, 5, 8) == pytest.approx(5 * per_row, rel=0.01)
    weights = 1000 * per_row
    rows = rows_for_fraction(CFG, 8, weights, 0.01)
    assert rows == 10
    # the registered-count floor wins over a too-small fraction
    assert rows_for_fraction(CFG, 8, weights, 0.0, n_registered=6) == 7
    # floor at base + 1, cap at 65
    assert rows_for_fraction(CFG, 8, weights, 0.0) == 2
    assert rows_for_fraction(CFG, 8, weights, 1e9) == 65


def test_memory_plan_accounts_adapter_and_grammar_pools():
    from langstream_tpu.serving.memory import plan_serving_memory

    base = plan_serving_memory(CFG, 4, 128)
    plan = plan_serving_memory(
        CFG, 4, 128, adapter_pool_rows=5, adapter_rank=8,
        grammar_slots=4, grammar_states=64,
    )
    assert plan.adapter_pool_bytes == lora_pool_bytes(CFG, 5, 8)
    from langstream_tpu.serving.constrain import grammar_pool_bytes

    assert plan.grammar_pool_bytes == grammar_pool_bytes(4, 64, CFG.vocab_size)
    assert plan.total_bytes == (
        base.total_bytes + plan.adapter_pool_bytes + plan.grammar_pool_bytes
    )
    assert "adapter-pool" in plan.summary()


def test_moe_config_gets_attention_only_adapters():
    moe = MODEL_PRESETS["tiny-moe-test"]
    host = init_random_lora(moe, 4, 0)
    assert set(host) == {"wq", "wk", "wv", "wo"}


def test_fleet_router_scores_adapter_affinity():
    """Pure-host router unit: with equal load and no prefix anywhere, the
    replica advertising the request's adapter wins; without an adapter in
    the request the tie falls to load as before."""
    from langstream_tpu.serving.fleet import FleetRouter

    class FakeReplica:
        def __init__(self, rid, adapters, load=0.0):
            self.replica_id = rid
            self.is_local = True
            self.url = f"local:{rid}"
            self._adapters = adapters
            self._load = load

        def fetch_beacon(self):
            return {
                "schema": "lstpu-beacon-v1",
                "id": self.replica_id,
                "at": 0.0,
                "load_score": self._load,
                "queue_wait_ema_s": 0.0,
                "draining": False,
                "quarantined": False,
                "prefixes": [],
                "adapters": list(self._adapters),
            }

    r1 = FakeReplica("r1", [], load=0.0)
    r2 = FakeReplica("r2", ["tenant-a"], load=0.1)
    router = FleetRouter([r1, r2], lam=1.0)
    router.refresh_all()
    # no adapter: lower load wins
    assert router.route([1, 2, 3]).replica_id == "r1"
    # adapter affinity outweighs the small load delta
    d = router.route([1, 2, 3], adapter="tenant-a")
    assert d.replica_id == "r2" and d.kind == "affinity"
    assert router.routed_adapter_total == 1
    assert router.stats()["fleet-routed-adapter-total"] == 1


def test_beacon_advertises_adapters_and_validates():
    from langstream_tpu.serving.fleet import beacon_from_engine, validate_beacon

    engine = make_engine()
    try:
        engine.generate(list(PROMPT), GenerationOptions(
            max_new_tokens=4, adapter="tenant-a",
        ), timeout=300)
        beacon = beacon_from_engine("r0", engine)
        assert validate_beacon(beacon)
        assert "tenant-a" in beacon["adapters"]
    finally:
        engine.stop()


def test_unknown_adapter_fails_request_not_engine():
    engine = make_engine()
    try:
        with pytest.raises(KeyError):
            # engine HAS a registry, but the name is unknown: resolution
            # fails the request at admission with KeyError
            bad = engine.submit(GenerationRequest(
                prompt_tokens=list(PROMPT),
                options=GenerationOptions(max_new_tokens=4, adapter="ghost"),
            ))
            bad.result(timeout=300)
        # the engine keeps serving
        ok = engine.generate(list(PROMPT), GREEDY, timeout=300)
        assert ok.tokens
    finally:
        engine.stop()


def test_pinned_full_pool_sheds_with_retry_after():
    """Transient saturation (every adapter row pinned by ACTIVE requests)
    must shed with ShedError + retry-after — a 429 the front door retries —
    not a hard error (the registries' documented contract)."""
    from langstream_tpu.serving.engine import ShedError

    three = ADAPTERS + [{"name": "tenant-c", "rank": 4, "scale": 1.0, "seed": 3}]
    engine = make_engine(adapters=three, adapter_pool_rows=3, max_batch=4)
    try:
        # park two LONG generations pinning both usable rows
        held = [
            engine.submit(GenerationRequest(
                prompt_tokens=list(PROMPT),
                options=GenerationOptions(max_new_tokens=400, adapter=name),
            ))
            for name in ("tenant-a", "tenant-b")
        ]
        with pytest.raises(ShedError) as exc:
            engine.generate(list(PROMPT), GenerationOptions(
                max_new_tokens=4, adapter="tenant-c",
            ), timeout=120)
        assert exc.value.retry_after_s > 0
        for r in held:
            r.cancel()
        for r in held:
            r.result(timeout=120)
        # rows free now: the shed tenant serves on retry
        ok = engine.generate(list(PROMPT), GenerationOptions(
            max_new_tokens=4, adapter="tenant-c",
        ), timeout=120)
        assert ok.tokens
    finally:
        engine.stop()


def test_adapter_without_registry_rejected_at_submit():
    engine = ServingEngine(
        CFG, PARAMS, max_batch=2, max_seq_len=128, decode_chunk=4,
        constrained_decoding="off",
    )
    engine.start()
    try:
        with pytest.raises(ValueError):
            engine.submit(GenerationRequest(
                prompt_tokens=list(PROMPT),
                options=GenerationOptions(max_new_tokens=4, adapter="x"),
            ))
    finally:
        engine.stop()


# ---------------------------------------------------------------------------
# engine e2e (slow: engine pairs — the chaos CI step runs these)
# ---------------------------------------------------------------------------


def _single_tenant_reference(adapter):
    engine = make_engine()
    try:
        return engine.generate(list(PROMPT), dataclasses.replace(
            GREEDY, adapter=adapter,
        ), timeout=300).tokens
    finally:
        engine.stop()


@pytest.mark.slow
def test_mixed_batch_token_exact_and_one_program():
    """ISSUE 10 acceptance: base + 2 adapter slots decode CONCURRENTLY in
    one batch; each slot's greedy tokens equal its single-tenant run, and
    the program count stays flat across the mix (paged layout: ONE decode
    program regardless of tenant composition)."""
    refs = {
        None: _single_tenant_reference(None),
        "tenant-a": _single_tenant_reference("tenant-a"),
        "tenant-b": _single_tenant_reference("tenant-b"),
    }
    assert refs["tenant-a"] != refs[None], "adapter must change the output"
    assert refs["tenant-b"] != refs["tenant-a"]

    engine = make_engine(precompile=True)
    try:
        warm = engine.generate(list(PROMPT), GREEDY, timeout=600)
        assert warm.tokens == refs[None]
        programs_before = engine.stats()["compiled_programs"]
        requests = {
            name: engine.submit(GenerationRequest(
                prompt_tokens=list(PROMPT),
                options=dataclasses.replace(GREEDY, adapter=name),
            ))
            for name in (None, "tenant-a", "tenant-b")
        }
        for name, req in requests.items():
            assert req.result(timeout=600).tokens == refs[name], name
        assert engine.stats()["compiled_programs"] == programs_before, (
            "mixed adapter batch compiled a new program"
        )
    finally:
        engine.stop()


@pytest.mark.slow
def test_adapter_swap_under_pool_pressure_stays_exact():
    """A 2-usable-row pool serving 3 tenants sequentially must swap (LRU)
    and every tenant's output stays equal to its dedicated-pool run."""
    three = ADAPTERS + [{"name": "tenant-c", "rank": 4, "scale": 2.0, "seed": 33}]
    big = make_engine(adapters=three, adapter_pool_rows=9)
    try:
        want = {
            n: big.generate(list(PROMPT), dataclasses.replace(
                GREEDY, adapter=n,
            ), timeout=300).tokens
            for n in ("tenant-a", "tenant-b", "tenant-c")
        }
    finally:
        big.stop()
    engine = make_engine(adapters=three, adapter_pool_rows=3)  # base + 2
    try:
        for name in ("tenant-a", "tenant-b", "tenant-c", "tenant-a"):
            got = engine.generate(list(PROMPT), dataclasses.replace(
                GREEDY, adapter=name,
            ), timeout=300).tokens
            assert got == want[name], name
        stats = engine.stats()
        assert stats["adapter-swaps-total"] >= 4  # c and the re-entrant a swapped
        assert stats["adapters-resident"] == 2
    finally:
        engine.stop()


@pytest.mark.slow
def test_adapter_fault_site_quarantines_victim_only():
    """The `adapter` chaos site corrupts ONE slot's dispatch-facing row;
    the integrity check must fail exactly that request (quarantine) while
    every other slot's tokens stay byte-identical to a fault-free run."""
    refs = {
        "tenant-a": _single_tenant_reference("tenant-a"),
        "tenant-b": _single_tenant_reference("tenant-b"),
    }
    engine = make_engine(
        fault_injector=FaultInjector("adapter@2", seed=0),
    )
    try:
        requests = [
            engine.submit(GenerationRequest(
                prompt_tokens=list(PROMPT),
                options=dataclasses.replace(GREEDY, adapter=name),
            ))
            for name in ("tenant-a", "tenant-b")
        ]
        outcomes = []
        for name, req in zip(("tenant-a", "tenant-b"), requests):
            try:
                outcomes.append((name, req.result(timeout=600).tokens, None))
            except RuntimeError as e:
                outcomes.append((name, None, e))
        victims = [o for o in outcomes if o[2] is not None]
        survivors = [o for o in outcomes if o[2] is None]
        assert len(victims) == 1, outcomes
        assert "adapter-row corruption" in str(victims[0][2])
        for name, tokens, _ in survivors:
            assert tokens == refs[name], f"survivor {name} lost exactness"
        stats = engine.stats()
        assert stats["quarantined-slots-total"] == 1
        assert stats["engine-restarts-total"] == 0
        # the incident artifact: an "adapter-quarantine" flight dump
        # naming the victim slot (registry-drift pass LSA403 — every
        # DUMP_REASONS entry gets a drill that actually fires it)
        dump = engine._obs.flight.last_dump
        assert dump is not None and dump["reason"] == "adapter-quarantine"
        assert dump["extra"]["slot"] in range(engine.max_batch)
        # the engine still serves the quarantined tenant afterwards
        again = engine.generate(list(PROMPT), dataclasses.replace(
            GREEDY, adapter=victims[0][0],
        ), timeout=600)
        assert again.tokens == refs[victims[0][0]]
    finally:
        engine.stop()


@pytest.mark.slow
def test_adapter_prefill_kv_carries_deltas_float_and_int8():
    """wk/wv adapters change the PROMPT's cache, not just logits: the same
    engine must produce different first tokens for base vs adapter on a
    prompt long enough that prefill dominates — on both KV dtypes."""
    long_prompt = list(range(5, 45))
    for kw in (
        {},
        {"config": dataclasses.replace(CFG, kv_cache_dtype="int8")},
    ):
        cfg = kw.pop("config", CFG)
        engine = ServingEngine(
            cfg, PARAMS, max_batch=2, max_seq_len=128, decode_chunk=4,
            adapters=ADAPTERS, constrained_decoding="off", **kw,
        )
        engine.start()
        try:
            base = engine.generate(list(long_prompt), GREEDY, timeout=300)
            tenant = engine.generate(list(long_prompt), dataclasses.replace(
                GREEDY, adapter="tenant-a",
            ), timeout=300)
            assert base.tokens != tenant.tokens, kw
        finally:
            engine.stop()


@pytest.mark.slow
def test_adapter_requests_never_touch_shared_prefix_cache():
    """Prefix aliasing is gated to base traffic: a tenant admission neither
    publishes its (delta-bearing) prefix nor aliases the base trie."""
    preamble = list(range(3, 3 + 70))  # crosses the 64 bucket boundary
    engine = make_engine(prefix_cache="auto", max_seq_len=256)
    try:
        base1 = engine.generate(preamble + [9], GREEDY, timeout=300)
        saved0 = engine.stats()["prefill-tokens-saved-total"]
        # tenant admission with the same preamble: MUST NOT reuse
        tenant = engine.generate(preamble + [9], dataclasses.replace(
            GREEDY, adapter="tenant-a",
        ), timeout=300)
        assert engine.stats()["prefill-tokens-saved-total"] == saved0
        # base admission still reuses the base-published prefix
        base2 = engine.generate(preamble + [11], GREEDY, timeout=300)
        assert engine.stats()["prefill-tokens-saved-total"] > saved0
        assert base1.tokens and tenant.tokens and base2.tokens
    finally:
        engine.stop()


@pytest.mark.slow
def test_tpu_serving_provider_end_to_end_agentic(run):
    """The whole stack: tpu-serving resource with `adapters:` configured +
    constrained-decoding auto; the completions service honors per-request
    `adapter` and `response-format` options (the option-whitelist lesson:
    a knob that doesn't survive _options() is dead code)."""
    import json as _json

    async def scenario():
        from langstream_tpu.ai.tpu_serving import TpuServingProvider
        from langstream_tpu.ai.provider import ChatMessage

        provider = TpuServingProvider({
            "model": "tiny-test",
            "tokenizer": "byte",
            "max-seq-len": 256,
            "max-batch": 2,
            "decode-chunk": 4,
            "adapters": ADAPTERS,
        })
        service = provider.get_completions_service({})
        base = await service.get_chat_completions(
            [ChatMessage(role="user", content="hi")],
            {"max-tokens": 8},
        )
        tenant = await service.get_chat_completions(
            [ChatMessage(role="user", content="hi")],
            {"max-tokens": 8, "adapter": "tenant-a"},
        )
        assert base.content != tenant.content
        structured = await service.get_chat_completions(
            [ChatMessage(role="user", content="extract")],
            {
                "max-tokens": 96,
                "response-format": {
                    "type": "json_schema",
                    "json_schema": {"schema": {
                        "type": "object",
                        "properties": {
                            "intent": {"type": "string", "maxLength": 8},
                            "ok": {"type": "boolean"},
                        },
                    }},
                },
            },
        )
        doc = _json.loads(structured.content)
        assert set(doc) == {"intent", "ok"}
        assert isinstance(doc["ok"], bool)
        stats = service.engine_stats()
        assert stats["constrained-requests-total"] == 1
        assert stats["adapters-resident"] >= 1
        await provider.close()

    run(scenario())


@pytest.mark.slow
@pytest.mark.parametrize("spec", [False, True], ids=["plain", "speculative"])
def test_acceptance_mixed_base_adapters_constrained_one_program(spec):
    """ISSUE 10 acceptance, whole: base + 2 adapter + constrained slots
    decode CONCURRENTLY in one batch; compiled_programs stays flat across
    the mix, every slot's greedy output equals its single-tenant run on an
    identically-configured engine, and the json_schema completion parses
    and validates — on the plain AND the speculative verify path."""
    import json as _json

    from langstream_tpu.serving.tokenizer import ByteTokenizer

    tok = ByteTokenizer()
    rf = {"type": "json_schema", "json_schema": {"schema": {
        "type": "object",
        "properties": {
            "name": {"type": "string", "maxLength": 8},
            "n": {"type": "integer"},
        },
    }}}
    base_opts = GenerationOptions(max_new_tokens=12)
    con_opts = GenerationOptions(max_new_tokens=80, response_format=rf)

    def build():
        engine = ServingEngine(
            CFG, PARAMS, max_batch=4, max_seq_len=256, decode_chunk=4,
            adapters=ADAPTERS, constrained_decoding="auto",
            grammar_tokenizer=tok, eos_token_id=tok.eos_token_id,
            speculation="auto" if spec else "off", speculation_tokens=4,
            precompile=True,
        )
        engine.start()
        return engine

    # per-tenant single-tenant references on an identical engine config
    ref = build()
    try:
        want = {
            "base": ref.generate(list(PROMPT), base_opts, timeout=600).tokens,
            "tenant-a": ref.generate(list(PROMPT), dataclasses.replace(
                base_opts, adapter="tenant-a"), timeout=600).tokens,
            "tenant-b": ref.generate(list(PROMPT), dataclasses.replace(
                base_opts, adapter="tenant-b"), timeout=600).tokens,
            "constrained": ref.generate(
                list(PROMPT), con_opts, timeout=600).tokens,
        }
    finally:
        ref.stop()
    assert len({tuple(v) for v in want.values()}) == 4  # all distinct

    engine = build()
    try:
        # warm every shape + grammar row the mixed batch will touch
        engine.generate(list(PROMPT), base_opts, timeout=600)
        engine.generate(list(PROMPT), con_opts, timeout=600)
        programs_before = engine.stats()["compiled_programs"]
        requests = {
            "base": engine.submit(GenerationRequest(
                prompt_tokens=list(PROMPT), options=base_opts)),
            "tenant-a": engine.submit(GenerationRequest(
                prompt_tokens=list(PROMPT),
                options=dataclasses.replace(base_opts, adapter="tenant-a"))),
            "tenant-b": engine.submit(GenerationRequest(
                prompt_tokens=list(PROMPT),
                options=dataclasses.replace(base_opts, adapter="tenant-b"))),
            "constrained": engine.submit(GenerationRequest(
                prompt_tokens=list(PROMPT), options=con_opts)),
        }
        got = {k: r.result(timeout=600) for k, r in requests.items()}
        for k in want:
            assert got[k].tokens == want[k], f"{k} diverged in the mix"
        doc = _json.loads(ByteTokenizer().decode(got["constrained"].tokens))
        assert set(doc) == {"name", "n"} and isinstance(doc["n"], int)
        assert got["constrained"].finish_reason == "stop"
        stats = engine.stats()
        assert stats["compiled_programs"] == programs_before, (
            "the mixed agentic batch compiled a new program"
        )
        if spec:
            assert stats["spec-verify-dispatches-total"] > 0
    finally:
        engine.stop()
