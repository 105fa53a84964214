"""First test coverage for the serving engine (`repro/serve/engine.py`):
chunked-prefill equivalence, iCh divisor adaptation, `generate` contracts,
deadline-based graceful degradation (DESIGN.md §2.9), and the ssm
family's incremental prefill (state-threaded chunks, scan-block aligned,
bit-identical to one-shot).

Runs on a reduced decoder config (repro.configs.reduced) so the whole
module is CPU-cheap; the model params are built once per module.
"""
import jax
import numpy as np
import pytest

from repro.configs import get_arch, reduced
from repro.models import model as M
from repro.serve.engine import Engine, EngineConfig
from repro.serve.metrics import ServeMetrics
from repro.serve.queue import Request, RequestState

ECFG = dict(max_seq=64, min_chunk=4)
# Chunked vs one-shot prefill logits: the same sums in a different order.
# On the CPU the largest difference is ~3e-7 on logits of order 1, so
# 1e-5 leaves headroom for reassociation yet fails a wrong mask, position
# or cache slot, which moves logits by O(0.1).
CHUNK_TOL = dict(rtol=1e-5, atol=1e-5)


@pytest.fixture(scope="module")
def tiny_model():
    cfg = reduced(get_arch("qwen2-1.5b"))
    params = M.init_params(cfg, jax.random.PRNGKey(0), max_seq=64)
    return cfg, params


@pytest.fixture()
def engine(tiny_model):
    cfg, params = tiny_model
    return Engine(cfg, params, EngineConfig(**ECFG))


def prompts_for(cfg, B=2, S=24, seed=2):
    return np.asarray(jax.random.randint(jax.random.PRNGKey(seed),
                                         (B, S), 0, cfg.vocab_size))


# ------------------------------------------------ chunked prefill

class TestChunkedPrefill:
    def test_matches_one_shot_bit_identical(self, tiny_model, engine):
        """Chunked prefill's final logits must match a one-shot prefill of
        the same prompt. Each chunk feeds only its own tokens into the
        growing KV cache (`models.prefill_extend`), so the attention and
        projection sums run over different splits than the one-shot
        program and XLA orders them differently — on the CPU and on the
        TPU alike. Chunking may move the logits by float32 rounding
        (CHUNK_TOL), never by a logic error."""
        cfg, params = tiny_model
        toks = prompts_for(cfg)
        logits, _, log = engine.prefill_chunked(toks)
        one_shot = Engine(cfg, params, EngineConfig(**ECFG))
        ref, _ = one_shot._prefill(params, {"tokens": np.asarray(toks)})
        np.testing.assert_allclose(np.asarray(logits), np.asarray(ref),
                                   **CHUNK_TOL)
        assert len(log) > 1  # S=24 with d_0=4, min_chunk=4 -> chunked

    def test_chunk_log_covers_prompt_exactly(self, tiny_model, engine):
        cfg, _ = tiny_model
        B, S = 2, 24
        _, _, log = engine.prefill_chunked(prompts_for(cfg, B, S))
        assert sum(rec["chunk"] for rec in log) == S
        assert all(rec["chunk"] >= 1 for rec in log)
        assert all(set(rec) == {"chunk", "dt", "d"} for rec in log)

    def test_outputs_independent_of_chunk_count(self, tiny_model):
        """Incremental prefill feeds each chunk into the growing cache
        (O(chunk) work per chunk, engine no longer re-runs the prefix);
        the final logits must agree within float32 summation-order
        rounding (CHUNK_TOL) however the prompt is cut. Divisors 1/3/8
        produce genuinely different chunk sequences."""
        cfg, params = tiny_model
        toks = prompts_for(cfg, B=2, S=24)
        logits, counts = [], []
        for d0 in (1.0, 3.0, 8.0):
            eng = Engine(cfg, params,
                         EngineConfig(max_seq=64, min_chunk=2,
                                      init_divisor=d0))
            lg, _, log = eng.prefill_chunked(toks)
            logits.append(np.asarray(lg))
            counts.append(len(log))
        assert len(set(counts)) > 1  # the splits really differed
        for lg in logits[1:]:
            np.testing.assert_allclose(lg, logits[0], **CHUNK_TOL)


# ------------------------------------------------ iCh divisor adaptation

def bare_engine(**overrides):
    """An Engine shell with only the state `_adapt`/`_next_chunk` touch —
    no model build needed to pin the divisor dynamics."""
    eng = Engine.__new__(Engine)
    eng.ecfg = EngineConfig(**{**ECFG, **overrides})
    eng.d = eng.ecfg.init_divisor
    eng.ks = []
    return eng


class TestAdapt:
    def steady(self, eng, rounds=6):
        for _ in range(rounds):
            eng._adapt(100, 1.0)

    def test_steady_throughput_keeps_divisor(self):
        eng = bare_engine()
        self.steady(eng)
        assert eng.d == eng.ecfg.init_divisor

    def test_fast_chunk_doubles_divisor(self):
        """Fast chunk (throughput above the mu + eps*mu band) -> HIGH ->
        d doubles -> next chunk shrinks, leaving slots for decode."""
        eng = bare_engine()
        self.steady(eng)
        eng._adapt(100, 0.01)
        assert eng.d == 2 * eng.ecfg.init_divisor

    def test_slow_chunk_halves_divisor(self):
        """Slow chunk (cache pressure, long context) -> LOW -> d halves ->
        next chunk grows to amortize dispatch."""
        eng = bare_engine()
        self.steady(eng)
        eng._adapt(100, 100.0)
        assert eng.d == eng.ecfg.init_divisor / 2

    def test_divisor_clamped_to_bounds(self):
        eng = bare_engine()
        for k in range(12):  # ever-faster chunks
            eng._adapt(100, 1.0 / 10 ** k)
        assert eng.d <= 64.0
        eng = bare_engine()
        for k in range(12):  # ever-slower chunks
            eng._adapt(100, 1.0 * 10 ** k)
        assert eng.d >= 1.0

    def test_next_chunk_contracts(self):
        eng = bare_engine()
        eng.d = 4.0
        assert eng._next_chunk(100) == 25
        assert eng._next_chunk(3) == 3      # never exceeds remaining
        eng.d = 64.0
        assert eng._next_chunk(100) == 4    # min_chunk floor


# ------------------------------------------------ generate

class TestGenerate:
    def test_output_shape_and_stats_contract(self, tiny_model, engine):
        cfg, _ = tiny_model
        B, S, n_new = 2, 24, 5
        out, stats = engine.generate(prompts_for(cfg, B, S), n_new=n_new)
        assert out.shape == (B, n_new)
        assert np.issubdtype(out.dtype, np.integer)
        assert (out >= 0).all() and (out < cfg.vocab_size).all()
        assert set(stats) == {"chunks", "d_final", "degraded", "n_shed",
                              "deadline_s"}
        assert stats["degraded"] is False and stats["n_shed"] == 0
        assert stats["deadline_s"] is None
        assert sum(rec["chunk"] for rec in stats["chunks"]) == S
        assert stats["d_final"] == engine.d

    def test_greedy_generate_deterministic(self, tiny_model):
        cfg, params = tiny_model
        toks = prompts_for(cfg)
        outs = [Engine(cfg, params, EngineConfig(**ECFG))
                .generate(toks, n_new=4)[0] for _ in range(2)]
        np.testing.assert_array_equal(outs[0], outs[1])

    def test_deadline_sheds_decode_steps(self, tiny_model, engine):
        """deadline_s=0 is already spent after prefill: the engine sheds
        all remaining decode steps, returns the partial output (at least
        the prefill argmax token) and flags the degradation."""
        cfg, _ = tiny_model
        n_new = 6
        out, stats = engine.generate(prompts_for(cfg), n_new=n_new,
                                     deadline_s=0.0)
        assert stats["degraded"] is True
        assert 1 <= out.shape[1] < n_new
        assert out.shape[1] + stats["n_shed"] == n_new
        assert stats["deadline_s"] == 0.0

    def test_generous_deadline_not_degraded(self, tiny_model, engine):
        cfg, _ = tiny_model
        out, stats = engine.generate(prompts_for(cfg), n_new=3,
                                     deadline_s=600.0)
        assert stats["degraded"] is False and stats["n_shed"] == 0
        assert out.shape[1] == 3

    def test_degraded_prefix_matches_full_run(self, tiny_model):
        """Degradation sheds FUTURE work only: the tokens a degraded run
        does emit are the same tokens the unconstrained run emits."""
        cfg, params = tiny_model
        toks = prompts_for(cfg)
        full, _ = Engine(cfg, params, EngineConfig(**ECFG)) \
            .generate(toks, n_new=6)
        part, stats = Engine(cfg, params, EngineConfig(**ECFG)) \
            .generate(toks, n_new=6, deadline_s=0.0)
        assert stats["degraded"] is True
        np.testing.assert_array_equal(part, full[:, :part.shape[1]])


# ------------------------------------------------ ssm incremental prefill

@pytest.fixture(scope="module")
def ssm_model():
    cfg = reduced(get_arch("xlstm-350m"), block_pattern=("X", "S"),
                  ssm_chunk=4)
    params = M.init_params(cfg, jax.random.PRNGKey(1), max_seq=64)
    return cfg, params


@pytest.fixture()
def ssm_engine(ssm_model):
    cfg, params = ssm_model
    return Engine(cfg, params, EngineConfig(**ECFG))


def assert_trees_equal(a, b):
    jax.tree.map(lambda x, y: np.testing.assert_array_equal(
        np.asarray(x), np.asarray(y)), a, b)


class TestSSMIncrementalPrefill:
    """The ssm family extends chunk to chunk through its O(1) recurrent
    block states (mLSTM matrix, sLSTM h/c) instead of re-running the
    prefix: O(chunk) per chunk, bit-identical to a one-shot prefill as
    long as chunk boundaries align to the scan-block quantum Q."""

    def test_family_supported(self, ssm_model):
        cfg, _ = ssm_model
        assert M.extend_cache_specs_ok(cfg)

    def test_hybrid_still_falls_back(self):
        assert not M.extend_cache_specs_ok(reduced(get_arch("zamba2-1.2b")))

    def test_matches_one_shot_bit_identical(self, ssm_model, ssm_engine):
        """Logits AND final recurrent states must equal a one-shot
        prefill bit-for-bit, including a final PARTIAL chunk (S=22 is not
        a multiple of Q=4, so the last chunk pads exactly like the
        one-shot scan pads its tail block)."""
        cfg, params = ssm_model
        toks = prompts_for(cfg, B=2, S=22)
        logits, cache, log = ssm_engine.prefill_chunked(toks)
        ref, ref_cache = ssm_engine._prefill(params,
                                             {"tokens": np.asarray(toks)})
        np.testing.assert_array_equal(np.asarray(logits), np.asarray(ref))
        assert_trees_equal(cache, ref_cache)
        assert len(log) > 1            # really chunked
        assert ssm_engine.n_prefill_fallbacks == 0

    def test_chunks_align_to_scan_quantum(self, ssm_model, ssm_engine):
        cfg, _ = ssm_model
        _, _, log = ssm_engine.prefill_chunked(prompts_for(cfg, B=1, S=22))
        chunks = [rec["chunk"] for rec in log]
        assert sum(chunks) == 22
        assert all(c % 4 == 0 for c in chunks[:-1])  # only the tail is partial

    def test_outputs_independent_of_chunk_count(self, ssm_model):
        cfg, params = ssm_model
        toks = prompts_for(cfg, B=2, S=24)
        logits, counts = [], []
        for d0 in (1.0, 3.0, 8.0):
            eng = Engine(cfg, params,
                         EngineConfig(max_seq=64, min_chunk=2,
                                      init_divisor=d0))
            lg, _, log = eng.prefill_chunked(toks)
            logits.append(np.asarray(lg))
            counts.append(len(log))
        assert len(set(counts)) > 1  # the splits really differed
        for lg in logits[1:]:
            np.testing.assert_array_equal(lg, logits[0])

    def test_generate_deterministic_across_divisors(self, ssm_model):
        """Identical prefill states mean identical decode streams no
        matter how the prompt was chunked."""
        cfg, params = ssm_model
        toks = prompts_for(cfg, B=2, S=20)
        outs = [Engine(cfg, params,
                       EngineConfig(max_seq=64, min_chunk=4,
                                    init_divisor=d0))
                .generate(toks, n_new=4)[0] for d0 in (1.0, 8.0)]
        np.testing.assert_array_equal(outs[0], outs[1])

    def test_request_chunk_step_quantizes_and_matches(self, ssm_model,
                                                      ssm_engine):
        """The batcher primitive rounds the policy's chunk up to a
        multiple of Q and the completed prefill's first token equals the
        one-shot argmax."""
        cfg, params = ssm_model
        toks = prompts_for(cfg, B=1, S=10)
        st = RequestState(request=Request(req_id=0, tokens=toks, n_new=1))
        ssm_engine.prefill_chunk_step(st, 5)    # -> rounded up to 8
        assert st.prefill_done == 8
        ssm_engine.prefill_chunk_step(st, 1)    # -> final partial chunk (2)
        assert st.prefill_done == 10
        ref, _ = ssm_engine._prefill(params, {"tokens": np.asarray(toks)})
        assert st.out_tokens == [int(np.argmax(np.asarray(ref)[0]))]


class TestPrefillFallbackVisibility:
    def test_fallback_chunks_counted(self):
        """hybrid (zamba2) still re-runs the prefix per chunk — every
        such chunk must land in the loud counter."""
        cfg = reduced(get_arch("zamba2-1.2b"))
        params = M.init_params(cfg, jax.random.PRNGKey(2), max_seq=64)
        eng = Engine(cfg, params, EngineConfig(**ECFG))
        _, _, log = eng.prefill_chunked(prompts_for(cfg, B=1, S=12))
        assert eng.n_prefill_fallbacks == len(log) > 1

    def test_metrics_counter_wired(self):
        m = ServeMetrics()
        assert m.n_prefill_fallback == 0
        assert "n_prefill_fallback" in m.summary()
        m.n_prefill_fallback = 3
        assert ServeMetrics.from_state(m.state_dict()) \
            .n_prefill_fallback == 3
