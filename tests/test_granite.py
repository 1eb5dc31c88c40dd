"""granite-4.0-h-micro: Mamba-2 layers beside attention, served through
the decode state, against the benchmark's plain reference.

Reduced size (one ten-layer period, d 128, f32) with the benchmark's
seeded weight draws (``bench/weights.py``), so the comparisons hold the
program to ``bench/configs/granite-4.0-h-micro.ref.py`` as the cell does
at full size.  In float32 the served path and the reference differ by
rounding only: logits within ``F32_SD`` of the reference's standard
deviation (the bf16 model differs by about an sd, so this limit tells
the precisions apart).  The mutation tests hold the comparison to the
cell's own limit (``check.max_gap_sd`` of its traffic file)."""

import dataclasses
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import check, harness, weights  # noqa: E402
from repro.model import model as M  # noqa: E402
from repro.model import recurrent as R  # noqa: E402
from repro.model.recurrent import RecState  # noqa: E402
from repro.serve import engine as E  # noqa: E402
from repro.serve.replay import teacher_forced_logits  # noqa: E402

jax.config.update("jax_platform_name", "cpu")

CONFIG = json.loads((ROOT / "bench/configs/granite-4.0-h-micro.json").read_text())
MIX = json.loads((ROOT / "bench/traffic/granite-4.0-h-micro.chat.json").read_text())
REF = harness.load_module(ROOT / "bench/configs/granite-4.0-h-micro.ref.py")
#: f32 served logits against the f32 reference, in sd of the position's
#: logits: rounding alone.
F32_SD = 1e-3
LENS, NEW, SLOTS, MAX_LEN = (40, 12), 6, 3, 128


@pytest.fixture(scope="module")
def model():
    cfg = dataclasses.replace(harness.arch_config(CONFIG).reduced(),
                              dtype="float32")
    params = weights.draw(M.abstract_params(cfg), 3)
    rng = np.random.default_rng(0)
    seqs = [rng.integers(0, cfg.vocab_size, n + NEW).astype(np.int32)
            for n in LENS]
    sizes = harness.sizes_of(cfg)
    want = []
    for s, n in zip(seqs, LENS):
        pad = np.zeros((1, MAX_LEN), np.int32)
        pad[0, :s.size] = s
        hid = REF.hidden(params, sizes, jnp.asarray(pad))[0, n - 1:n - 1 + NEW]
        want.append(REF.head(params, sizes, hid))
    return cfg, params, seqs, want


def _step(cfg):
    return jax.jit(lambda p, st, tok, ln, m: M.decode_step(
        p, cfg, st, tok, ln, token_mask=m, last_only=True, max_len=MAX_LEN))


def _zero_ssm_state(state):
    """The carried SSM state lost at the admit/window boundary."""
    return jax.tree.map(
        lambda n: RecState(jnp.zeros_like(n.h), n.conv)
        if isinstance(n, RecState) else n, state,
        is_leaf=lambda n: isinstance(n, RecState))


def served_logits(cfg, params, seqs, after_admit=None):
    """The prompts admitted in one masked call (rows 0..), then NEW-1
    tokens teacher-forced: the engine's admit and window programs' model
    calls.  Returns each row's (NEW, V) logits."""
    return teacher_forced_logits(params, cfg, seqs, LENS, NEW, slots=SLOTS,
                                 max_len=MAX_LEN, after_admit=after_admit)


def logit_sd(got, want):
    return max(float(jnp.max(jnp.max(jnp.abs(g - w), -1) / jnp.std(w, -1)))
               for g, w in zip(got, want))


def argmax_gap(got, want):
    """The cell's measure: the program's greedy token's distance below
    the reference's best, in sd of the position's reference logits."""
    return max(float(jnp.max(check._gap_stats(
        w, jnp.argmax(g, -1).astype(jnp.int32)))) for g, w in zip(got, want))


def test_published_layout_and_sizes():
    cfg = harness.arch_config(CONFIG)
    kinds = ["attn" if k == "attention" else k for k in CONFIG["layer_types"]]
    assert list(cfg.layer_kinds) == kinds
    assert (cfg.mamba_d_inner, cfg.mamba_conv_dim) == (4096, 4352)
    assert 3.1e9 < cfg.param_count() < 3.3e9
    st = M.abstract_decode_state(cfg, 1, 1024)
    rec = [n for n in jax.tree.leaves(
        st, is_leaf=lambda n: isinstance(n, RecState))
        if isinstance(n, RecState)]
    ssm = sum(np.prod(n.h.shape) * n.h.dtype.itemsize for n in rec)
    assert ssm == 36 * 64 * 64 * 128 * 4      # 75.5 MB of f32 state a slot


def test_block_stateless_matches_stepped(model):
    cfg, params, _, _ = model
    p = jax.tree.map(lambda a: a[0], params["decoder"]["scanned"][0]["mamba"])
    x = jax.random.normal(jax.random.key(1), (2, 20, cfg.d_model))
    full, _ = R.apply_mamba2_block(p, x, cfg)
    st = jax.tree.map(lambda a: jnp.zeros(a.shape, a.dtype),
                      M._layer_state_shape(cfg, "mamba", 2, 64))
    steps = []
    for t in range(x.shape[1]):
        o, st = R.apply_mamba2_block(p, x[:, t:t + 1], cfg, state=st)
        steps.append(o)
    np.testing.assert_allclose(np.concatenate(steps, 1), full,
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("use_kernel", [False, True])
def test_chunk_dispatch_matches_windows(model, use_kernel):
    """80 tokens in one stateful call (over the window limit: the chunk
    form) against the same tokens in two 40-token windows."""
    cfg, params, _, _ = model
    p = jax.tree.map(lambda a: a[0], params["decoder"]["scanned"][0]["mamba"])
    x = jax.random.normal(jax.random.key(2), (2, 80, cfg.d_model))
    st0 = jax.tree.map(lambda a: jnp.zeros(a.shape, a.dtype),
                       M._layer_state_shape(cfg, "mamba", 2, 128))
    one, s1 = R.apply_mamba2_block(p, x, cfg, state=st0, use_kernel=use_kernel)
    a, s2 = R.apply_mamba2_block(p, x[:, :40], cfg, state=st0,
                                 use_kernel=use_kernel)
    b, s2 = R.apply_mamba2_block(p, x[:, 40:], cfg, state=s2,
                                 use_kernel=use_kernel)
    np.testing.assert_allclose(one, jnp.concatenate([a, b], 1),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(s1.h, s2.h, rtol=1e-4, atol=1e-4)
    np.testing.assert_array_equal(s1.conv, s2.conv)


def test_prefill_then_decode_matches_reference(model):
    cfg, params, seqs, want = model
    got = served_logits(cfg, params, seqs)
    assert logit_sd(got, want) <= F32_SD
    assert argmax_gap(got, want) <= F32_SD


@pytest.mark.parametrize("mutation", ["zero_state", "no_D"])
def test_mutations_fail_the_cells_limit(model, mutation):
    """Losing the carried SSM state at the admit/window boundary, or
    dropping ``D x``, fails the comparison that decides ``correct``."""
    cfg, params, seqs, want = model
    prm = params
    if mutation == "no_D":
        prm = jax.tree.map(lambda a: a, params)
        for blk in prm["decoder"]["scanned"]:
            if "mamba" in blk:
                blk["mamba"] = dict(blk["mamba"], D=jnp.zeros_like(blk["mamba"]["D"]))
    got = served_logits(cfg, prm, seqs, after_admit=_zero_ssm_state
                        if mutation == "zero_state" else None)
    gap = argmax_gap(got, want)
    print(mutation, gap)
    assert gap > MIX["check"]["max_gap_sd"]


def test_admission_leaves_other_slots_alone(model):
    """Admitting into slot 2 of a busy state changes nothing of slots 0
    and 1: their SSM states bit for bit, their next logits bit for bit."""
    cfg, params, seqs, _ = model
    step = _step(cfg)
    p_max = 64
    tok = np.zeros((SLOTS, p_max), np.int32)
    mask = np.zeros((SLOTS, p_max), bool)
    for i, (s, n) in enumerate(zip(seqs, LENS)):
        tok[i, :n], mask[i, :n] = s[:n], True
    state = M.init_decode_state(cfg, SLOTS, MAX_LEN, insert_window=p_max)
    _, state = step(params, state, jnp.asarray(tok), jnp.int32(0),
                    jnp.asarray(mask))
    cur = jnp.asarray([[s[n]] for s, n in zip(seqs, LENS)] + [[0]], jnp.int32)
    ln = jnp.asarray(list(LENS) + [0], jnp.int32)
    live = jnp.asarray([[True], [True], [False]])
    before, _ = step(params, state, cur, ln, live)

    row = jnp.asarray([False, False, True])
    admitted = E._reset_slot_rows(state, row)
    tok2 = np.zeros((SLOTS, p_max), np.int32)
    tok2[2, :30] = np.arange(30)
    mask2 = np.zeros((SLOTS, p_max), bool)
    mask2[2, :30] = True
    _, admitted = step(params, admitted, jnp.asarray(tok2), jnp.int32(0),
                       jnp.asarray(mask2))
    after, _ = step(params, admitted, cur, ln, live)
    np.testing.assert_array_equal(np.asarray(after[:2]), np.asarray(before[:2]))
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(admitted)):
        if a.ndim >= 2 and a.shape[1] == SLOTS and a.dtype == jnp.float32:
            np.testing.assert_array_equal(np.asarray(a[:, :2]),
                                          np.asarray(b[:, :2]))


def test_session_tokens_are_the_references_greedy(model):
    """``ServeEngine`` / ``ServeSession`` greedy output, checked by the
    benchmark's own comparison against the reference."""
    cfg, params, _, _ = model
    rng = np.random.default_rng(4)
    reqs = [E.Request(tokens=rng.integers(0, cfg.vocab_size, n).astype(np.int32),
                      max_new_tokens=m) for n, m in ((9, 7), (70, 5), (30, 9))]
    eng = E.ServeEngine(cfg, params, max_len=MAX_LEN, decode_window=4)
    outs = eng.serve(reqs, slots=2)
    got = check.compare(REF, params, harness.sizes_of(cfg),
                        [(r.tokens, np.asarray(o)) for r, o in zip(reqs, outs)],
                        MAX_LEN)
    assert got["tokens"] == 21 and got["max_gap_sd"] <= F32_SD


def _admit_args(cfg, state, slot, prompt, p, slots):
    """Operands of the engine's admit program admitting ``prompt`` into
    ``slot`` of a state whose other slots are busy."""
    tok = np.zeros((slots, p), np.int32)
    tok[slot, :prompt.size] = prompt
    row = np.arange(slots) == slot
    plen = np.where(row, prompt.size, 0).astype(np.int32)
    vec = jnp.zeros(slots, jnp.int32)
    return [state, tok, jnp.asarray(row), jnp.asarray(plen), vec,
            jnp.asarray(np.full(slots, 20, np.int32)), vec,
            jnp.full(slots, 99, jnp.int32), jnp.arange(slots, dtype=jnp.int32),
            jnp.asarray(~row), jnp.zeros((slots, 1), jnp.int32),
            jax.random.key_data(jax.random.key(0))]


def test_row_admission_matches_the_all_slots_one(model):
    """An admission over ``admit_rows`` slots (the admitted one and a busy
    filler, masked) leaves the filler and every slot it did not pick bit
    for bit, and gives the admitted slot the state and first token the
    all-slots admission gives (f32: rounding alone between the two batch
    sizes)."""
    cfg, params, seqs, _ = model
    slots, p = 4, 64
    cfg2 = dataclasses.replace(cfg, admit_rows=2)
    eng = E.ServeEngine(cfg2, params, max_len=MAX_LEN, decode_window=4)
    tok = np.zeros((slots, p), np.int32)
    mask = np.zeros((slots, p), bool)
    for i, (s, n) in enumerate(zip(seqs, LENS)):
        tok[i, :n], mask[i, :n] = s[:n], True
    state = M.init_decode_state(cfg, slots, MAX_LEN, insert_window=p)
    _, state = _step(cfg)(params, state, jnp.asarray(tok), jnp.int32(0),
                          jnp.asarray(mask))
    prompt = np.arange(3, 33, dtype=np.int32)
    copy = lambda: jax.tree.map(jnp.copy, state)  # noqa: E731
    args = _admit_args(cfg, copy(), 3, prompt, p, slots)
    full = eng._admit_step(p, 0.0, 0, None)(params, *args)
    args = _admit_args(cfg, copy(), 3, prompt, p, slots)
    args[1] = args[1][[3, 1]]
    rows = eng._admit_step(p, 0.0, 0, None, 2)(
        params, *args, jnp.asarray([3, 1], jnp.int32))
    for a, f, r in zip(*(jax.tree.leaves(s) for s in (state, full[0], rows[0]))):
        ax = next(i for i, n in enumerate(a.shape) if n == slots)
        a, f, r = (np.moveaxis(np.asarray(x), ax, 0) for x in (a, f, r))
        np.testing.assert_array_equal(r[:3], a[:3])
        np.testing.assert_allclose(r[3], f[3], rtol=1e-5, atol=1e-6)
    assert int(rows[5][3]) == int(full[5][3])
    for i in (1, 2, 3, 4):
        np.testing.assert_array_equal(np.asarray(rows[i]), np.asarray(full[i]))


def test_session_admits_at_most_admit_rows_a_step(model):
    """Four requests due at once in four slots: with ``admit_rows`` 2 the
    session admits two a step, and the greedy tokens are those of the
    all-slots admission."""
    cfg, params, _, _ = model
    rng = np.random.default_rng(5)
    reqs = [E.Request(tokens=rng.integers(0, cfg.vocab_size, n).astype(np.int32),
                      max_new_tokens=m)
            for n, m in ((9, 7), (70, 8), (30, 9), (41, 6))]
    outs, admissions = [], []
    for r in (0, 2):
        eng = E.ServeEngine(dataclasses.replace(cfg, admit_rows=r), params,
                            max_len=MAX_LEN, decode_window=4)
        sess = E.ServeSession(eng, reqs, slots=4)
        sess.step()
        admissions.append((sess.stats["admissions"],
                           int(sess.active_np.sum())))
        while sess.busy:
            sess.step()
        outs.append([list(map(int, o)) for o in sess.outputs])
        sess.close()
    assert admissions == [(1, 4), (1, 2)]
    assert outs[0] == outs[1]


def _ssm_rows(state, slot):
    """Slot ``slot``'s SSM state (each Mamba layer's h and conv tail)."""
    rec = [n for n in jax.tree.leaves(
        state, is_leaf=lambda n: isinstance(n, RecState))
        if isinstance(n, RecState)]
    return [np.take(np.asarray(a), slot, axis=a.ndim - d).view(np.uint32)
            for n in rec for a, d in ((n.h, 4), (n.conv, 3))]


def test_served_window_leaves_dead_slots_alone(model, monkeypatch):
    """A served window with the SSD decode kernel forced (interpreted):
    slot 1 idle, slot 2 finishing after two of the four tokens.  Slot 1's
    SSM state stays bit for bit through the window, slot 2's from the
    token it goes dead (against a two-token window), and the live slots'
    tokens are those of the oracle path."""
    cfg, params, seqs, _ = model
    p_max = 64
    prompts = [s[:n] for s, n in zip(seqs, LENS)] + [np.arange(5, 25)]
    tok = np.zeros((SLOTS, p_max), np.int32)
    mask = np.zeros((SLOTS, p_max), bool)
    for i, s in enumerate(prompts):
        tok[i, :s.size], mask[i, :s.size] = s, True
    state = M.init_decode_state(cfg, SLOTS, MAX_LEN, insert_window=p_max)
    _, state = _step(cfg)(params, state, jnp.asarray(tok), jnp.int32(0),
                          jnp.asarray(mask))
    i32 = lambda v: jnp.asarray(v, jnp.int32)  # noqa: E731
    args = (i32([[seqs[0][LENS[0]]], [seqs[1][LENS[1]]], [7]]),
            i32([s.size for s in prompts]), i32([0, 0, 0]), i32([9, 9, 2]),
            jnp.asarray([True, False, True]), i32([0, 1, 2]),
            jax.random.key_data(jax.random.key(0)))

    def window(k, kernel):
        monkeypatch.setattr(R, "on_tpu", lambda: kernel)
        eng = E.ServeEngine(cfg, params, max_len=MAX_LEN, decode_window=k)
        out = eng._serve_window(k, 0.0, 0, None)(
            params, jax.tree.map(jnp.copy, state), *args)
        return out[0], np.asarray(out[6]), np.asarray(out[7])

    st4, toks, emits = window(4, True)
    st2, _, _ = window(2, True)
    _, want, want_emits = window(4, False)
    assert emits[:, 1].sum() == 0 and list(emits[:, 2]) == [1, 1, 0, 0]
    for a, b in zip(_ssm_rows(st4, 1), _ssm_rows(state, 1)):
        np.testing.assert_array_equal(a, b)
    for a, b in zip(_ssm_rows(st4, 2), _ssm_rows(st2, 2)):
        np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(emits, want_emits)
    np.testing.assert_array_equal(toks[emits], want[emits])
