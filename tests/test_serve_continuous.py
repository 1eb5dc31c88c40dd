"""Continuous-batching serve tests: scheduler parity vs solo lockstep
runs, ragged-prompt prefill masking, dead-slot state freezing, in-window
sampling determinism, EOS slot recycling / admission ordering, and the
ring-slack trace-time contract."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_config
from repro.model import model as M
from repro.model.attention import KVCache
from repro.serve.engine import Request, ServeEngine, make_cache_prefill_step

jax.config.update("jax_platform_name", "cpu")

ARCHS = ["rwkv6-1.6b", "gemma3-1b", "recurrentgemma-2b", "granite-4.0-h-micro"]


def _setup(arch, seed=0):
    cfg = get_config(arch).reduced()
    params = M.init_params(cfg, jax.random.key(0))
    return cfg, params, np.random.default_rng(seed)


def _ragged_requests(rng, cfg, spec):
    return [
        Request(
            tokens=rng.integers(0, cfg.vocab_size, (pl,)).astype(np.int32),
            max_new_tokens=nn,
        )
        for pl, nn in spec
    ]


def _solo_greedy(cfg, params, req, max_len=96, decode_window=4):
    """The lockstep oracle: this request alone, batch of one."""
    eng = ServeEngine(cfg, params, max_len=max_len,
                      decode_window=decode_window)
    full = eng.generate(jnp.asarray(req.tokens)[None, :], req.max_new_tokens)
    return np.asarray(full[0, np.asarray(req.tokens).size:])


SPEC = [(5, 9), (12, 3), (7, 14), (3, 6), (9, 11)]


class TestContinuousParity:
    """Acceptance: ragged prompts + ragged budgets, every request's greedy
    output bit-identical to running it alone."""

    @pytest.mark.parametrize("arch", ARCHS)
    def test_greedy_bit_identical_to_solo(self, arch):
        cfg, params, rng = _setup(arch)
        reqs = _ragged_requests(rng, cfg, SPEC)
        eng = ServeEngine(cfg, params, max_len=96, decode_window=4)
        outs = eng.serve(reqs, slots=2)
        assert eng.last_serve_stats["admissions"] >= 2  # slots were recycled
        for i, req in enumerate(reqs):
            want = _solo_greedy(cfg, params, req)
            np.testing.assert_array_equal(outs[i], want)

    def test_parity_across_slot_counts_and_windows(self):
        cfg, params, rng = _setup("rwkv6-1.6b", seed=3)
        reqs = _ragged_requests(rng, cfg, SPEC)
        want = None
        for slots, k in ((1, 1), (2, 4), (3, 8), (5, 2)):
            eng = ServeEngine(cfg, params, max_len=96, decode_window=k)
            outs = eng.serve(reqs, slots=slots)
            if want is None:
                want = outs
            else:
                for a, b in zip(want, outs):
                    np.testing.assert_array_equal(a, b)


class TestRaggedPrefill:
    """Bugfix: pad tokens of a batched ragged prompt must contribute
    nothing to KV caches or recurrent states."""

    @pytest.mark.parametrize("arch", ARCHS)
    def test_masked_prefill_matches_solo(self, arch):
        cfg, params, rng = _setup(arch, seed=1)
        plens = [4, 9, 6]
        p_max = max(plens)
        prompts = np.zeros((len(plens), p_max), np.int32)
        for b, pl in enumerate(plens):
            prompts[b, :pl] = rng.integers(0, cfg.vocab_size, pl)
        prefill = make_cache_prefill_step(cfg, last_only=True, max_len=64)
        state = M.init_decode_state(cfg, batch=len(plens), max_len=64,
                                    insert_window=p_max)
        lg, state = prefill(params, state, jnp.asarray(prompts),
                            jnp.asarray(plens, jnp.int32))
        for b, pl in enumerate(plens):
            st = M.init_decode_state(cfg, batch=1, max_len=64,
                                     insert_window=p_max)
            lgs, _ = make_cache_prefill_step(cfg, last_only=True, max_len=64)(
                params, st, jnp.asarray(prompts[b : b + 1, :pl]))
            np.testing.assert_array_equal(np.asarray(lg[b, 0]),
                                          np.asarray(lgs[0, 0]))

    def test_unmasked_ragged_prefill_was_polluted(self):
        # The bug this PR fixes: without the mask, pad tokens enter the
        # state and shift the short request's logits.
        cfg, params, rng = _setup("rwkv6-1.6b", seed=2)
        pl, p_max = 4, 12
        prompt = rng.integers(0, cfg.vocab_size, pl).astype(np.int32)
        padded = np.zeros((1, p_max), np.int32)
        padded[0, :pl] = prompt
        prefill = make_cache_prefill_step(cfg, last_only=True, max_len=64)
        s1 = M.init_decode_state(cfg, batch=1, max_len=64, insert_window=p_max)
        lg_mask, _ = prefill(params, s1, jnp.asarray(padded),
                             jnp.asarray([pl], jnp.int32))
        s2 = M.init_decode_state(cfg, batch=1, max_len=64, insert_window=p_max)
        lg_pad, _ = prefill(params, s2, jnp.asarray(padded))  # no mask
        s3 = M.init_decode_state(cfg, batch=1, max_len=64, insert_window=p_max)
        lg_solo, _ = prefill(params, s3, jnp.asarray(prompt[None]))
        np.testing.assert_array_equal(np.asarray(lg_mask[0, 0]),
                                      np.asarray(lg_solo[0, 0]))
        # The unmasked padded run reads its logits at the pad position —
        # a different distribution entirely.
        assert not np.array_equal(np.asarray(lg_pad[0, 0]),
                                  np.asarray(lg_solo[0, 0]))

    def test_generate_with_prompt_lengths_matches_solo(self):
        cfg, params, rng = _setup("gemma3-1b", seed=5)
        plens = np.asarray([5, 8])
        p_max, n_new = 8, 6
        prompts = np.zeros((2, p_max), np.int32)
        for b in range(2):
            prompts[b, : plens[b]] = rng.integers(0, cfg.vocab_size, plens[b])
        eng = ServeEngine(cfg, params, max_len=64, decode_window=4)
        out = eng.generate(jnp.asarray(prompts), n_new,
                           prompt_lengths=jnp.asarray(plens, jnp.int32))
        for b in range(2):
            solo = ServeEngine(cfg, params, max_len=64, decode_window=4)
            want = solo.generate(
                jnp.asarray(prompts[b : b + 1, : plens[b]]), n_new)
            np.testing.assert_array_equal(
                np.asarray(out[b, p_max:]),
                np.asarray(want[0, plens[b]:]))


class TestDeadSlotFreeze:
    """The window scan must leave a finished slot's state bit-identical
    (jnp.where-frozen), not merely approximately unchanged."""

    @pytest.mark.parametrize("arch", ARCHS)
    def test_masked_slot_state_untouched(self, arch):
        cfg, params, rng = _setup(arch, seed=4)
        b = 3
        state = M.init_decode_state(cfg, batch=b, max_len=64)
        # Fill with a couple of live steps so the frozen state is nonzero.
        toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (b, 1)), jnp.int32)
        _, state = M.decode_step(params, cfg, state, toks, jnp.int32(0))
        _, state = M.decode_step(params, cfg, state, toks, jnp.int32(1))
        before = jax.tree.leaves(state)
        mask = jnp.asarray([True, False, True])[:, None]
        _, state2 = M.decode_step(params, cfg, state, toks,
                                  jnp.asarray([2, 2, 2], jnp.int32),
                                  token_mask=mask)
        after = jax.tree.leaves(state2)
        for x, y in zip(before, after):
            x, y = np.asarray(x), np.asarray(y)
            if x.ndim == 0:
                continue
            # Batch axis may be 0 (unstacked) or 1 (layer-stacked): the
            # dead slot's rows must be bit-identical on both layouts.
            got_hit = False
            for ax in (0, 1):
                if ax < x.ndim and x.shape[ax] == 3:
                    np.testing.assert_array_equal(
                        np.take(x, 1, axis=ax), np.take(y, 1, axis=ax))
                    got_hit = True
                    break
            assert got_hit, f"no batch axis found for shape {x.shape}"


class TestInWindowSampling:
    def test_deterministic_across_decode_windows(self):
        cfg, params, rng = _setup("rwkv6-1.6b", seed=6)
        reqs = _ragged_requests(rng, cfg, [(5, 7), (9, 4), (3, 10)])
        outs = {}
        for k in (1, 3, 8):
            eng = ServeEngine(cfg, params, max_len=64, decode_window=k)
            outs[k] = eng.serve(reqs, slots=2, temperature=0.8, top_k=16,
                                seed=7)
        for k in (3, 8):
            for a, b in zip(outs[1], outs[k]):
                np.testing.assert_array_equal(a, b)

    def test_seed_and_slot_invariance(self):
        cfg, params, rng = _setup("rwkv6-1.6b", seed=7)
        reqs = _ragged_requests(rng, cfg, [(4, 6), (6, 6), (8, 6)])
        eng = ServeEngine(cfg, params, max_len=64, decode_window=4)
        a = eng.serve(reqs, slots=2, temperature=1.0, seed=11)
        b = eng.serve(reqs, slots=3, temperature=1.0, seed=11)
        c = eng.serve(reqs, slots=2, temperature=1.0, seed=12)
        for u, v in zip(a, b):
            np.testing.assert_array_equal(u, v)  # slot-count invariant
        assert any(not np.array_equal(u, v) for u, v in zip(a, c)), (
            "different seeds produced identical streams")

    def test_top_k_restricts_support(self):
        # With top_k=1, temperature sampling degenerates to greedy.
        cfg, params, rng = _setup("rwkv6-1.6b", seed=8)
        reqs = _ragged_requests(rng, cfg, [(5, 8), (7, 5)])
        eng = ServeEngine(cfg, params, max_len=64, decode_window=4)
        greedy = eng.serve(reqs, slots=2, temperature=0.0)
        topk1 = eng.serve(reqs, slots=2, temperature=1.3, top_k=1, seed=5)
        for u, v in zip(greedy, topk1):
            np.testing.assert_array_equal(u, v)


class TestEosAndAdmission:
    def test_eos_frees_slot_and_truncates(self):
        cfg, params, rng = _setup("rwkv6-1.6b", seed=9)
        reqs = _ragged_requests(rng, cfg, [(5, 12), (8, 12), (4, 12)])
        eng = ServeEngine(cfg, params, max_len=64, decode_window=4)
        base = eng.serve(reqs, slots=2)
        # Pick an EOS id that actually occurs mid-stream in request 0.
        eos = int(base[0][len(base[0]) // 2])
        outs = eng.serve(reqs, slots=2, eos_id=eos)
        for b0, be in zip(base, outs):
            b0 = list(b0)
            if eos in b0:
                np.testing.assert_array_equal(be, b0[: b0.index(eos) + 1])
            else:
                np.testing.assert_array_equal(be, b0)
        assert any(eos in list(b0) for b0 in base)

    def test_admission_ordering_fifo(self):
        # More requests than slots: slot recycling must admit in arrival
        # order, and every request must complete with its own budget.
        cfg, params, rng = _setup("rwkv6-1.6b", seed=10)
        spec = [(4, 3), (5, 9), (6, 2), (3, 7), (7, 4), (5, 5)]
        reqs = _ragged_requests(rng, cfg, spec)
        eng = ServeEngine(cfg, params, max_len=64, decode_window=2)
        outs = eng.serve(reqs, slots=2)
        assert [len(o) for o in outs] == [nn for _, nn in spec]
        assert eng.last_serve_stats["admissions"] >= 3
        for i, req in enumerate(reqs):
            want = _solo_greedy(cfg, params, req, max_len=64, decode_window=2)
            np.testing.assert_array_equal(outs[i], want)

    def test_more_slots_than_requests(self):
        cfg, params, rng = _setup("rwkv6-1.6b", seed=12)
        reqs = _ragged_requests(rng, cfg, [(5, 4), (7, 6)])
        eng = ServeEngine(cfg, params, max_len=64, decode_window=4)
        outs = eng.serve(reqs, slots=4)  # clipped to len(requests)
        for i, req in enumerate(reqs):
            want = _solo_greedy(cfg, params, req, max_len=64, decode_window=4)
            np.testing.assert_array_equal(outs[i], want)

    def test_budget_validation(self):
        cfg, params, _ = _setup("rwkv6-1.6b")
        eng = ServeEngine(cfg, params, max_len=16)
        with pytest.raises(ValueError, match="max_new_tokens"):
            eng.serve([Request(tokens=np.zeros(4, np.int32),
                               max_new_tokens=0)])
        with pytest.raises(ValueError, match="non-empty"):
            eng.serve([Request(tokens=np.zeros(0, np.int32),
                               max_new_tokens=4)])

    def test_oversized_request_is_shed_not_fatal(self):
        """A request that can't fit max_len is load to refuse (typed shed
        outcome), not a ValueError that aborts every other request."""
        cfg, params, _ = _setup("rwkv6-1.6b")
        eng = ServeEngine(cfg, params, max_len=16, decode_window=2)
        good = Request(tokens=np.arange(4, dtype=np.int32),
                       max_new_tokens=4)
        bad = Request(tokens=np.zeros(10, np.int32), max_new_tokens=10)
        outs = eng.serve([good, bad, good], slots=2)
        assert outs[1].outcome == "shed" and outs[1].size == 0
        assert eng.last_serve_stats["shed"] == 1
        solo = eng.serve([good], slots=1)
        for i in (0, 2):
            assert outs[i].outcome in ("ok", "eos")
            np.testing.assert_array_equal(outs[i].tokens, solo[0].tokens)


class TestRingSlackContract:
    """Bugfix: a decode window wider than the local-attention ring slack
    used to silently corrupt output; it must now fail at trace time."""

    def test_slack_deficient_window_raises(self):
        cfg, params, _ = _setup("gemma3-1b")
        # insert_window=1 ring (attn_window slots), max_len well above it:
        # an 8-token window would wrap the ring mid-window.
        state = M.init_decode_state(cfg, batch=1, max_len=256)
        tokens = jnp.zeros((1, 8), jnp.int32)
        with pytest.raises(ValueError, match="insert_window"):
            M.decode_step(params, cfg, state, tokens, jnp.int32(0),
                          max_len=256)

    def test_capped_ring_is_allowed_with_max_len(self):
        # A ring capped at max_len never wraps — max_len= vouches for it.
        cfg, params, _ = _setup("gemma3-1b")
        state = M.init_decode_state(cfg, batch=1, max_len=48, insert_window=8)
        tokens = jnp.zeros((1, 8), jnp.int32)
        logits, _ = M.decode_step(params, cfg, state, tokens, jnp.int32(0),
                                  max_len=48)
        assert bool(jnp.isfinite(logits).all())

    def test_uncapped_slackful_ring_needs_no_max_len(self):
        cfg, params, _ = _setup("gemma3-1b")
        state = M.init_decode_state(cfg, batch=1, max_len=256, insert_window=8)
        tokens = jnp.zeros((1, 8), jnp.int32)
        logits, _ = M.decode_step(params, cfg, state, tokens, jnp.int32(0))
        assert bool(jnp.isfinite(logits).all())


class TestKVCacheLengths:
    def test_per_request_lengths_in_state(self):
        cfg, _, _ = _setup("gemma3-1b")
        state = M.init_decode_state(cfg, batch=3, max_len=32)
        caches = [s for s in jax.tree.leaves(
            state, is_leaf=lambda x: isinstance(x, KVCache))
            if isinstance(s, KVCache)]
        assert caches
        for c in caches:
            assert c.length.shape[-1] == 3  # per-request, maybe (L, B)


class TestServeBatchStepsModel:
    """cost_model.serve_batch_steps: the scheduler's slot-step accounting."""

    def test_continuous_never_undercounts_budget_one(self):
        from repro.core.cost_model import serve_batch_steps

        # Budget-1 requests finish at admission; the simulator must keep
        # admitting instead of bailing with work still queued.
        useful, lock, cont = serve_batch_steps([1, 50], 1, 4)
        assert useful == 51 and cont >= 50
        useful, lock, cont = serve_batch_steps([1, 1, 5], 2, 1)
        assert useful == 7 and cont >= 4

    def test_ragged_workload_favors_continuous(self):
        from repro.core.cost_model import serve_batch_steps

        useful, lock, cont = serve_batch_steps(
            [56, 8, 48, 12, 60, 10, 40, 16], 2, 4)
        assert useful == 250
        assert cont < lock          # the acceptance regime
        assert useful <= cont       # can't beat perfect utilization

    def test_uniform_workload_is_a_wash(self):
        from repro.core.cost_model import serve_batch_steps

        useful, lock, cont = serve_batch_steps([16, 16, 16, 16], 2, 4)
        assert lock == cont  # no raggedness: the barrier costs nothing
