"""Grammar-constrained decoding (serving/constrain.py + the mask path in
serving/sampling.py + the engine's DFA plumbing) — ISSUE 10's grammar half.

The layers under test, bottom-up:
- regex → byte DFA: matching semantics vs Python `re` on accept/reject
  sets (the compiler is hand-rolled; `re` is the oracle);
- JSON schema → regex → token DFA: every schema-constrained completion
  parses AND validates, and bounded primitives force termination;
- the sampler fold: masked sample()/speculative_verify() behavior incl.
  the NaN-guard ordering (a grammar's -inf must not read as a fault);
- engine e2e: the device mask path is token-exact vs an INDEPENDENT
  host-masked reference loop (transformer.prefill + decode_step with
  numpy masking — no engine code on the reference side).

Engine-heavy tests are `slow` (chaos CI runs them; tier-1 keeps the pure
host units)."""

import dataclasses
import json
import re as _re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from langstream_tpu.models.configs import MODEL_PRESETS, GenerationOptions
from langstream_tpu.models.transformer import (
    decode_step,
    init_params,
    make_kv_cache,
    prefill,
)
from langstream_tpu.serving.constrain import (
    DEAD,
    GrammarError,
    GrammarRegistry,
    TokenDFA,
    compile_response_format,
    compile_token_dfa,
    grammar_pool_bytes,
    schema_to_regex,
    verify_states,
    _nfa_to_byte_dfa,
    _regex_to_nfa,
)
from langstream_tpu.serving.engine import GenerationRequest, ServingEngine
from langstream_tpu.serving.sampling import sample
from langstream_tpu.serving.tokenizer import ByteTokenizer

CFG = dataclasses.replace(MODEL_PRESETS["tiny-test"], dtype="float32")
PARAMS = init_params(CFG, jax.random.PRNGKey(0))
TOK = ByteTokenizer()

SCHEMA = {
    "type": "object",
    "properties": {
        "name": {"type": "string", "maxLength": 8},
        "n": {"type": "integer"},
    },
}
RF = {"type": "json_schema", "json_schema": {"schema": SCHEMA}}


def make_engine(**kw):
    kw.setdefault("max_batch", 2)
    kw.setdefault("max_seq_len", 256)
    kw.setdefault("decode_chunk", 4)
    kw.setdefault("grammar_tokenizer", TOK)
    kw.setdefault("eos_token_id", TOK.eos_token_id)
    engine = ServingEngine(kw.pop("config", CFG), PARAMS, **kw)
    engine.start()
    return engine


# ---------------------------------------------------------------------------
# regex → byte DFA (oracle: python re)
# ---------------------------------------------------------------------------


def _dfa_accepts(pattern: str, text: str) -> bool:
    byte_next, accepting = _nfa_to_byte_dfa(*_regex_to_nfa(pattern))
    s = 0
    for b in text.encode("utf-8"):
        s = int(byte_next[s, b])
        if s < 0:
            return False
    return s in accepting


@pytest.mark.parametrize("pattern,accepts,rejects", [
    ("abc", ["abc"], ["ab", "abcd", "abd", ""]),
    ("a*b", ["b", "ab", "aaab"], ["a", "ba"]),
    ("a+b?", ["a", "ab", "aaa"], ["b", "", "abb"]),
    ("(ab|cd)+", ["ab", "cdab"], ["a", "abc", ""]),
    ("[0-9]+", ["0", "42"], ["", "4x"]),
    ("[^x]y", ["ay", "zy"], ["xy", "y"]),
    (r"-?(0|[1-9][0-9]*)", ["0", "-7", "120"], ["01", "-", "+3"]),
    ("a{2,4}", ["aa", "aaa", "aaaa"], ["a", "aaaaa"]),
    ("(ab){0,2}c", ["c", "abc", "ababc"], ["abababc", "ab"]),
    (r"\{x\}", ["{x}"], ["x", "{x"]),
])
def test_regex_dfa_matches_python_re(pattern, accepts, rejects):
    # sanity: our accept/reject sets agree with python's re
    for text in accepts:
        assert _re.fullmatch(pattern, text), (pattern, text)
        assert _dfa_accepts(pattern, text), (pattern, text)
    for text in rejects:
        assert not _re.fullmatch(pattern, text), (pattern, text)
        assert not _dfa_accepts(pattern, text), (pattern, text)


def test_regex_parser_rejects_malformed():
    # non-ASCII inside a CLASS is a GrammarError (classes are byte sets;
    # multi-byte UTF-8 can't join one) — never an IndexError escaping to
    # the caller; non-ASCII LITERALS outside classes byte-chain fine
    for bad in ("(", "a{", "a{3,1}", "[", "a)", "*a", "\\", "[€]", "[a-€]"):
        with pytest.raises(GrammarError):
            _regex_to_nfa(bad)
    _regex_to_nfa("€")  # literal multi-byte char is legal


# ---------------------------------------------------------------------------
# JSON schema → regex
# ---------------------------------------------------------------------------


def test_schema_to_regex_samples_match():
    pattern = schema_to_regex(SCHEMA)
    assert _re.fullmatch(pattern, '{"name":"bob","n":42}')
    assert _re.fullmatch(pattern, '{"name":"","n":-1}')
    assert not _re.fullmatch(pattern, '{"name":"bob"}')  # all props required
    assert not _re.fullmatch(pattern, '{"n":42,"name":"bob"}')  # fixed order
    enum = schema_to_regex({"enum": ["red", "green", 3]})
    assert _re.fullmatch(enum, '"red"') and _re.fullmatch(enum, "3")
    arr = schema_to_regex({"type": "array", "items": {"type": "integer"},
                           "maxItems": 2})
    assert _re.fullmatch(arr, "[]") and _re.fullmatch(arr, "[1,2]")
    assert not _re.fullmatch(arr, "[1,2,3]")
    # maxItems: 1 emits the epsilon repetition {0,0} — must compile, and
    # accept exactly zero or one element
    one = compile_response_format(
        {"type": "json_schema", "schema": {
            "type": "array", "items": {"type": "integer"}, "maxItems": 1,
        }},
        TOK, CFG.vocab_size, None,
    )
    s = 0
    for ch in "[7]":
        s = one.advance(s, ord(ch))
        assert s >= 0, ch
    assert one.is_complete(s) or s in one.accepting
    assert one.advance(one.advance(0, ord("[")), ord("]")) >= 0  # empty []


def test_token_byte_table_cached_per_tokenizer():
    from langstream_tpu.serving.constrain import _token_byte_table

    tok = ByteTokenizer()
    b1, l1 = _token_byte_table(tok, CFG.vocab_size)
    b2, l2 = _token_byte_table(tok, CFG.vocab_size)
    assert b1 is b2 and l1 is l2  # grammar-independent: built once


def test_schema_to_regex_rejects_unsupported():
    with pytest.raises(GrammarError):
        schema_to_regex({"type": "object", "properties": {}})
    with pytest.raises(GrammarError):
        schema_to_regex({"oneOf": [{"type": "string"}]})


# ---------------------------------------------------------------------------
# token DFA
# ---------------------------------------------------------------------------


def test_token_dfa_legality_and_advance():
    dfa = compile_token_dfa("(yes|no)", TOK, CFG.vocab_size, TOK.eos_token_id)
    s0 = 0
    legal0 = {t for t in range(CFG.vocab_size) if dfa.next[s0, t] >= 0}
    assert legal0 == {ord("y"), ord("n")}
    s1 = dfa.advance(s0, ord("n"))
    s2 = dfa.advance(s1, ord("o"))
    assert dfa.is_complete(s2) or s2 in dfa.accepting
    # byte ids past the tokenizer vocab are never legal mid-grammar
    assert dfa.next[s0, 300] == DEAD


def test_token_dfa_complete_state_self_loops_not_dead():
    """Sink-accept states self-loop on EVERY token (the no-all-masked-row
    invariant that keeps the NaN guard quiet); the host finishes on entry
    so the loop tokens are never delivered."""
    dfa = compile_token_dfa("ab", TOK, CFG.vocab_size, None)
    s = dfa.advance(dfa.advance(0, ord("a")), ord("b"))
    assert dfa.is_complete(s)
    assert np.all(dfa.next[s] == s)


def test_token_dfa_eos_legal_only_at_accepting_states():
    dfa = compile_token_dfa("[0-9]{1,3}", TOK, CFG.vocab_size, TOK.eos_token_id)
    assert dfa.next[0, TOK.eos_token_id] == DEAD  # nothing matched yet
    s1 = dfa.advance(0, ord("7"))
    assert dfa.next[s1, TOK.eos_token_id] >= 0  # "7" is a full match


def test_verify_states_carries_last_legal_past_illegal_draft():
    dfa = compile_token_dfa("[0-9]+", TOK, CFG.vocab_size, None)
    states = verify_states(dfa, 0, [ord("1"), ord("x"), ord("2")])
    assert len(states) == 4
    assert states[1] == dfa.advance(0, ord("1"))
    assert states[2] == states[1]  # 'x' illegal → carry
    assert all(s >= 0 for s in states)


def test_response_format_spellings_and_errors():
    flat = compile_response_format(
        {"type": "json_schema", "schema": SCHEMA}, TOK, CFG.vocab_size, None
    )
    nested = compile_response_format(RF, TOK, CFG.vocab_size, None)
    assert np.array_equal(flat.next, nested.next)
    with pytest.raises(GrammarError):
        compile_response_format({"type": "xml"}, TOK, CFG.vocab_size, None)
    with pytest.raises(GrammarError):
        compile_response_format({"type": "regex"}, TOK, CFG.vocab_size, None)


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------


def test_grammar_registry_cache_residency_and_lru():
    reg = GrammarRegistry(TOK, CFG.vocab_size, None, slots=2, max_states=64)
    d1 = reg.compile({"type": "regex", "regex": "ab"})
    assert reg.compile({"type": "regex", "regex": "ab"}) is d1  # cache hit
    assert reg.compiled_total == 1
    r1 = reg.acquire(d1)
    d2 = reg.compile({"type": "regex", "regex": "cd"})
    r2 = reg.acquire(d2)
    assert r1 != r2 and reg.resident == 2
    d3 = reg.compile({"type": "regex", "regex": "ef"})
    with pytest.raises(GrammarError):
        reg.acquire(d3)  # both rows pinned
    reg.release(d1)
    r3 = reg.acquire(d3)
    assert r3 == r1 and reg.swaps_total == 3  # LRU row recycled


def test_grammar_registry_rejects_oversized_grammar():
    reg = GrammarRegistry(TOK, CFG.vocab_size, None, slots=1, max_states=4)
    with pytest.raises(GrammarError):
        reg.compile({"type": "regex", "regex": "abcdefghij"})


def test_grammar_pool_bytes_arithmetic():
    # packed planes: bits [G+1, S, ceil(V/32)] uint32 + defaults [G+1, S]
    # int32 + exception key/next [G+1, E] int32 each
    assert grammar_pool_bytes(4, 128, 512, 64) == 5 * (
        128 * 16 * 4 + 128 * 4 + 2 * 64 * 4
    )
    assert grammar_pool_bytes(0, 128, 512) == 0
    # the word count rounds UP for vocabs that are not multiples of 32
    assert grammar_pool_bytes(1, 2, 33, 1) == 2 * (2 * 2 * 4 + 2 * 4 + 8)


def test_packed_pool_beats_dense_by_24x_at_256k_vocab():
    """ISSUE 20 acceptance: the packed pool term is ≤ 1/24 of the dense
    [G+1, S, V] int32 pool at a 256k vocab — asserted at BOTH the
    arithmetic and the memory-plan layer."""
    slots, states, vocab = 64, 128, 256000
    dense = (slots + 1) * states * vocab * 4
    packed = grammar_pool_bytes(slots, states, vocab)
    assert packed * 24 <= dense
    from langstream_tpu.serving.memory import plan_serving_memory

    big = dataclasses.replace(CFG, vocab_size=vocab)
    plan = plan_serving_memory(
        big, 4, 128, grammar_slots=slots, grammar_states=states
    )
    assert plan.grammar_pool_bytes == packed
    assert plan.grammar_pool_bytes * 24 <= dense


def test_pack_next_table_roundtrip_matches_dense():
    """The packed product reproduces the dense table exactly: bitmask
    expansion == legality, and the default-successor + sorted-exceptions
    probe (replayed with numpy searchsorted — the same formula the device
    advance uses) == dense next for every LEGAL token."""
    from langstream_tpu.serving.constrain import _EXC_SENTINEL, pack_next_table

    dfa = compile_response_format(RF, TOK, CFG.vocab_size, TOK.eos_token_id)
    bits, defaults, exc_key, exc_next = pack_next_table(dfa.next)
    n_states, vocab = dfa.next.shape
    n_words = (vocab + 31) // 32
    assert bits.shape == (n_states, n_words) and bits.dtype == np.uint32
    expanded = (
        (bits[..., None] >> np.arange(32, dtype=np.uint32)) & 1
    ).reshape(n_states, n_words * 32)[:, :vocab].astype(bool)
    assert np.array_equal(expanded, dfa.next >= 0)
    assert np.all(np.diff(exc_key) >= 0)  # sorted: searchsorted-probeable
    padded_keys = np.concatenate([exc_key, [np.int64(_EXC_SENTINEL)]])
    for s in range(n_states):
        for t in np.nonzero(dfa.next[s] >= 0)[0]:
            key = np.int64(s) * vocab + t
            i = np.searchsorted(padded_keys, key, side="left")
            got = (
                int(exc_next[i])
                if i < len(exc_key) and padded_keys[i] == key
                else int(defaults[s])
            )
            assert got == dfa.next[s, t], (s, t)


def test_registry_uploads_packed_rows_device_exact():
    """LRU swap-under-pressure keeps pool rows EXACT: after churning more
    grammars than rows through a 1-slot pool, the resident row's device
    planes equal the grammar's host-packed product (the token-exactness
    substrate: the fused chunks read only these planes)."""
    reg = GrammarRegistry(TOK, CFG.vocab_size, None, slots=1, max_states=64)
    for pat in ("ab", "cd", "[0-9]+"):
        dfa = reg.compile({"type": "regex", "regex": pat})
        row = reg.acquire(dfa)
        bits, defaults, exc_key, exc_next = dfa.packed()
        pool_bits, pool_defaults, pool_key, pool_next = reg.pool
        n = dfa.n_states
        assert np.array_equal(np.asarray(pool_bits)[row, :n], bits)
        assert np.array_equal(np.asarray(pool_defaults)[row, :n], defaults)
        e = len(exc_key)
        assert np.array_equal(
            np.asarray(pool_key)[row, :e], exc_key.astype(np.int32)
        )
        assert np.array_equal(np.asarray(pool_next)[row, :e], exc_next)
        # padded exception tail stays at the sentinel (no false probe hits)
        from langstream_tpu.serving.constrain import _EXC_SENTINEL

        assert np.all(np.asarray(pool_key)[row, e:] == _EXC_SENTINEL)
        reg.release(dfa)
    assert reg.swaps_total == 3


def test_pool_exhaustion_at_default_slots_raises_documented_error():
    """Satellite: at the 64-slot default, pinning every row makes the
    65th acquire raise the documented GrammarError (the shed path's
    trigger), and releasing one row swaps-in fine again."""
    reg = GrammarRegistry(TOK, CFG.vocab_size, None, max_states=16)
    assert reg.slots == 64  # the new default
    dfas = []
    for i in range(64):
        d = reg.compile({"type": "regex", "regex": f"x{i:02d}"})
        reg.acquire(d)
        dfas.append(d)
    assert reg.resident == 64
    extra = reg.compile({"type": "regex", "regex": "z+"})
    with pytest.raises(GrammarError, match="pinned"):
        reg.acquire(extra)
    reg.release(dfas[0])
    assert reg.acquire(extra) >= 1  # LRU recycled the released row


def test_registry_exceptions_capacity_contract():
    """A grammar needing more exception rows than the pool carries fails
    at compile with the documented knob name (mirrors grammar-states)."""
    reg = GrammarRegistry(
        TOK, CFG.vocab_size, None, slots=1, max_states=64, max_exceptions=1
    )
    with pytest.raises(GrammarError, match="grammar-exceptions"):
        reg.compile({"type": "regex", "regex": "(ab|cd|ef)"})


def test_registry_refcounts_survive_cross_thread_release():
    """acquire()/release() are lock-guarded (release runs from the
    request _finalize hook off the engine thread): hammering the pair
    from many threads must leave refs at exactly zero — an unguarded
    `refs -= 1` loses decrements under the race."""
    import threading

    reg = GrammarRegistry(TOK, CFG.vocab_size, None, slots=2, max_states=64)
    dfa = reg.compile({"type": "regex", "regex": "ab"})
    n, rounds = 8, 200
    barrier = threading.Barrier(n)

    def churn():
        barrier.wait()
        for _ in range(rounds):
            reg.acquire(dfa)
            reg.release(dfa)

    threads = [threading.Thread(target=churn) for _ in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert reg._by_key[dfa.key].refs == 0


def test_zero_slots_unified_disabled_contract():
    """Satellite: grammar_pool_bytes(slots<=0) == 0 and the registry's
    slots<1 rejection are ONE contract — the registry's error names it,
    and an engine built with grammar_slots=0 disables constrained
    decoding instead of silently coercing a 1-slot pool."""
    assert grammar_pool_bytes(0, 128, 512) == 0
    assert grammar_pool_bytes(-3, 128, 512) == 0
    with pytest.raises(ValueError, match="disables constrained decoding"):
        GrammarRegistry(TOK, CFG.vocab_size, None, slots=0)
    engine = ServingEngine(
        CFG, PARAMS, max_batch=2, max_seq_len=128,
        constrained_decoding="auto", grammar_slots=0, grammar_tokenizer=TOK,
        eos_token_id=TOK.eos_token_id,
    )
    assert engine._constrain_reg is None
    assert engine.stats()["constrained-decoding"] is False
    assert engine.stats()["grammar-pool-bytes"] == 0
    with pytest.raises(ValueError):
        engine.submit(GenerationRequest(
            prompt_tokens=TOK.encode("x"),
            options=GenerationOptions(response_format=RF),
        ))


# ---------------------------------------------------------------------------
# sampler fold
# ---------------------------------------------------------------------------


def test_sample_mask_restricts_and_preserves_nan_guard():
    logits = jnp.asarray(np.linspace(0, 1, 16, dtype=np.float32)[None, :])
    allowed = np.zeros((1, 16), bool)
    allowed[0, 3] = True
    out = sample(
        logits, jax.random.PRNGKey(0), jnp.zeros(1), jnp.zeros(1, jnp.int32),
        jnp.ones(1), jnp.asarray(allowed),
    )
    assert int(out[0]) == 3  # only legal token wins despite lower logit
    # a genuinely non-finite row still trips the sentinel THROUGH the mask
    poisoned = logits.at[0, 5].set(jnp.nan)
    out = sample(
        poisoned, jax.random.PRNGKey(0), jnp.zeros(1),
        jnp.zeros(1, jnp.int32), jnp.ones(1), jnp.asarray(allowed),
    )
    assert int(out[0]) == -1


def test_sampled_path_respects_mask_distribution():
    """Masked sampled tokens land ONLY on legal ids and follow the masked
    softmax (coarse chi-square-free check on frequencies)."""
    rng = np.random.default_rng(0)
    logits = jnp.asarray(rng.normal(size=(1, 8)).astype(np.float32))
    allowed = np.zeros((1, 8), bool)
    allowed[0, [2, 5]] = True
    counts = {2: 0, 5: 0}
    n = 400
    for i in range(n):
        out = sample(
            logits, jax.random.PRNGKey(i), jnp.ones(1) * 0.8,
            jnp.zeros(1, jnp.int32), jnp.ones(1), jnp.asarray(allowed),
        )
        counts[int(out[0])] += 1
    masked = np.where(allowed[0], np.asarray(logits[0]) / 0.8, -np.inf)
    probs = np.exp(masked - masked.max())
    probs /= probs.sum()
    assert abs(counts[2] / n - probs[2]) < 0.1


# ---------------------------------------------------------------------------
# engine e2e (slow)
# ---------------------------------------------------------------------------


def _host_masked_reference(prompt, dfa: TokenDFA, max_new: int,
                           config=CFG) -> list[int]:
    """INDEPENDENT reference: prefill + per-step decode through the raw
    transformer entry points, masking fetched logits with numpy and taking
    the argmax host-side — no engine, no device mask path."""
    cache = make_kv_cache(config, 1, 256)
    tokens = np.zeros((1, 64), np.int32)
    tokens[0, : len(prompt)] = prompt
    logits, cache = prefill(
        PARAMS, jnp.asarray(tokens), jnp.asarray([len(prompt)]), cache, config
    )
    out: list[int] = []
    state = 0
    position = len(prompt)
    current = None
    while len(out) < max_new:
        row = np.asarray(logits)[0] if current is None else np.asarray(
            current
        )[0]
        legal = dfa.next[state] >= 0
        row = np.where(legal[: row.shape[0]], row, -np.inf)
        token = int(np.argmax(row))
        if token == TOK.eos_token_id:
            break
        out.append(token)
        state = dfa.advance(state, token)
        if dfa.is_complete(state):
            break
        current, cache = decode_step(
            PARAMS, jnp.asarray([token]), jnp.asarray([position]), cache,
            config,
        )
        current = current[None, :] if current.ndim == 1 else current
        position += 1
    return out


@pytest.mark.slow
@pytest.mark.parametrize("kv", ["float", "int8"])
def test_constrained_greedy_token_exact_vs_host_masked_reference(kv):
    config = CFG if kv == "float" else dataclasses.replace(
        CFG, kv_cache_dtype="int8"
    )
    dfa = compile_response_format(RF, TOK, CFG.vocab_size, TOK.eos_token_id)
    prompt = TOK.encode("Hi")
    want = _host_masked_reference(prompt, dfa, 64, config=config)
    engine = make_engine(config=config)
    try:
        got = engine.generate(list(prompt), GenerationOptions(
            max_new_tokens=64, response_format=RF,
        ), timeout=600)
        assert got.tokens == want
        assert got.finish_reason == "stop"
        json.loads(TOK.decode(got.tokens))
    finally:
        engine.stop()


@pytest.mark.slow
def test_constrained_completions_parse_and_validate_including_sampled():
    engine = make_engine(max_batch=4)
    try:
        results = []
        for temp in (0.0, 0.9, 1.3):
            r = engine.generate(TOK.encode("Go"), GenerationOptions(
                max_new_tokens=96, temperature=temp, response_format=RF,
            ), timeout=600)
            results.append(r)
        for r in results:
            assert r.finish_reason == "stop"
            doc = json.loads(TOK.decode(r.tokens))
            assert set(doc) == {"name", "n"}
            assert isinstance(doc["name"], str) and len(doc["name"]) <= 8
            assert isinstance(doc["n"], int)
        assert engine.stats()["constrained-requests-total"] == 3
    finally:
        engine.stop()


@pytest.mark.slow
def test_constrained_prefix_warm_admission_token_exact():
    """Constraints compose with prefix reuse (grammar masks only the
    GENERATED side): a warm admission's constrained output must equal the
    cold one's."""
    preamble = TOK.encode("x" * 80)
    engine = make_engine(prefix_cache="auto", max_batch=2)
    try:
        opts = GenerationOptions(max_new_tokens=64, response_format=RF)
        cold = engine.generate(list(preamble), opts, timeout=600)
        saved0 = engine.stats()["prefill-tokens-saved-total"]
        warm = engine.generate(list(preamble), opts, timeout=600)
        assert engine.stats()["prefill-tokens-saved-total"] > saved0, (
            "second admission did not hit the prefix cache"
        )
        assert warm.tokens == cold.tokens
        json.loads(TOK.decode(warm.tokens))
    finally:
        engine.stop()


@pytest.mark.slow
def test_constrained_mixed_with_free_slots_one_program():
    """A constrained slot and a free-form slot decode concurrently; the
    free slot's output is byte-identical to a grammar-free engine's, and
    the program count stays flat across the mixed batch."""
    free_engine = make_engine(constrained_decoding="off")
    try:
        want_free = free_engine.generate(
            TOK.encode("Hello"), GenerationOptions(max_new_tokens=16),
            timeout=600,
        ).tokens
    finally:
        free_engine.stop()
    engine = make_engine(max_batch=2, precompile=True)
    try:
        warm = engine.generate(
            TOK.encode("warm"), GenerationOptions(max_new_tokens=8),
            timeout=600,
        )
        assert warm.tokens
        # also warm the constrained grammar (its row upload is a program)
        engine.generate(TOK.encode("warm"), GenerationOptions(
            max_new_tokens=32, response_format=RF,
        ), timeout=600)
        programs_before = engine.stats()["compiled_programs"]
        con = engine.submit(GenerationRequest(
            prompt_tokens=TOK.encode("Go"),
            options=GenerationOptions(max_new_tokens=96, response_format=RF),
        ))
        free = engine.submit(GenerationRequest(
            prompt_tokens=TOK.encode("Hello"),
            options=GenerationOptions(max_new_tokens=16),
        ))
        assert free.result(timeout=600).tokens == want_free
        json.loads(TOK.decode(con.result(timeout=600).tokens))
        assert engine.stats()["compiled_programs"] == programs_before
    finally:
        engine.stop()


@pytest.mark.slow
def test_response_format_rejected_when_constrain_off():
    engine = make_engine(constrained_decoding="off")
    try:
        with pytest.raises(ValueError):
            engine.submit(GenerationRequest(
                prompt_tokens=TOK.encode("x"),
                options=GenerationOptions(response_format=RF),
            ))
    finally:
        engine.stop()
