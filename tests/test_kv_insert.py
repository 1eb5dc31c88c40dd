"""The in-place dense KV insert against the whole-cache oracle.

``attention._insert_in_place`` writes only what a decode window adds to
one layer of a stacked ``(L, B, Hkv, S, Dh)`` cache: one row per request
at a decode step, positions ``[0, t)`` at an admission.
``attention._masked_insert``, which rewrites the whole layer, is kept as
its oracle (and as the path of caches whose ``S`` is not a whole number
of 128 lanes).  Every case compares the two bit for bit, on the XLA
scatter path and on the Pallas row kernel (interpret mode); then whole
greedy streams, the trace size of the serve programs, and their donation.
"""

import jax
import jax.extend.core as jcore
import jax.numpy as jnp
import numpy as np
import pytest

import repro.kernels.kv_insert.ops as kv_ops
from repro.configs.registry import get_config
from repro.kernels.kv_insert.kernel import kv_row_insert, rows_per_step
from repro.model import attention as A
from repro.model import model as M
from repro.serve import engine as E
from repro.serve.engine import Request, ServeEngine

L, B, H, D, S = 3, 6, 2, 64, 256
BF16 = jnp.bfloat16


@pytest.fixture(params=["xla", "kernel"])
def path(request, monkeypatch):
    """The write as off a TPU (XLA scatter), or the Pallas row kernel
    (interpret mode), as on one."""
    if request.param == "kernel":
        monkeypatch.setattr(kv_ops, "on_tpu", lambda: True)
        monkeypatch.setattr(kv_ops, "interpret_default", lambda: True)
    return request.param


def _stack(seed):
    rng = np.random.default_rng(seed)
    return tuple(jnp.asarray(rng.standard_normal((L, B, H, S, D)), BF16)
                 for _ in range(2))


def _new(seed, t):
    rng = np.random.default_rng(seed)
    return tuple(jnp.asarray(rng.standard_normal((B, H, t, D)), jnp.float32)
                 for _ in range(2))


def _oracle(caches, news, layer, length, mask):
    return tuple(
        c.at[layer].set(A._masked_insert(c[layer], n, length, mask))
        for c, n in zip(caches, news))


def _assert_same(got, want):
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


#: Per-request fill before a decode step: the first slot, a middle one,
#: the last slot (S - 1), ring wrap-around (S and past 2S), a block edge.
LENGTHS = np.array([0, 77, S - 1, S, 2 * S + 5, 128], np.int32)


@pytest.mark.parametrize("layer", [0, L - 1])
@pytest.mark.parametrize("masked", [(), (1, 4), tuple(range(B))])
def test_window_step_writes_one_row_per_request(path, layer, masked):
    caches, news = _stack(1), _new(2, 1)
    length = jnp.asarray(LENGTHS)
    mask = jnp.asarray([[b not in masked] for b in range(B)])
    got = jax.jit(A._insert_in_place)(
        A.KVLayer(*caches, length, jnp.int32(layer)), *news, mask)
    _assert_same(got, _oracle(caches, news, layer, length, mask))
    # Masked (finished) rows and every other layer are bit-identical.
    others = [i for i in range(L) if i != layer]
    for g, c in zip(got, caches):
        g, c = np.asarray(g), np.asarray(c)
        np.testing.assert_array_equal(g[:, list(masked)], c[:, list(masked)])
        np.testing.assert_array_equal(g[others], c[others])


def test_window_step_without_mask_and_lockstep_length(path):
    caches, news = _stack(3), _new(4, 1)
    for length in (jnp.int32(S + 3), jnp.asarray(LENGTHS)):
        got = A._insert_in_place(A.KVLayer(*caches, length, 1), *news)
        _assert_same(got, _oracle(caches, news, 1, length, None))


@pytest.mark.parametrize("t", [40, 128, S])
def test_admission_writes_its_prompt_from_zero(path, t):
    """After the reset, admitted rows start at 0 and every other row is
    masked: positions [0, t) of the admitted rows take the prompt (up to
    each one's length), and nothing else moves."""
    caches, news = _stack(5), _new(6, t)
    admitted = np.array([False, True, False, True, True, False])
    plen = np.array([0, t, 0, 1, t // 2 + 3, 0])
    length = jnp.asarray(np.where(admitted, 0, LENGTHS))
    mask = jnp.asarray(admitted[:, None] & (np.arange(t)[None] < plen[:, None]))
    got = jax.jit(A._insert_in_place)(
        A.KVLayer(*caches, length, jnp.int32(2)), *news, mask)
    _assert_same(got, _oracle(caches, news, 2, length, mask))
    for g, c in zip(got, caches):
        g, c = np.asarray(g), np.asarray(c)
        np.testing.assert_array_equal(g[:, ~admitted], c[:, ~admitted])
        np.testing.assert_array_equal(g[:, :, :, t:], c[:, :, :, t:])


def test_window_not_from_zero_rewrites_the_layer(path):
    """A multi-token window at nonzero fills (not an admission) takes the
    oracle's whole-layer rewrite, ring wrap-around included."""
    caches, news = _stack(7), _new(8, 4)
    length = jnp.asarray(LENGTHS)
    mask = jnp.asarray(np.arange(4)[None] < np.array([4, 2, 4, 0, 3, 1])[:, None])
    got = jax.jit(A._insert_in_place)(
        A.KVLayer(*caches, length, jnp.int32(0)), *news, mask)
    _assert_same(got, _oracle(caches, news, 0, length, mask))


def test_row_kernel_direct():
    """The kernel on its own view ``(L, B, Hkv, Dh, S)``: a slot of S
    writes nothing; grid steps of several requests each."""
    caches, news = _stack(9), _new(10, 1)
    k_t, v_t = (jnp.swapaxes(c, -1, -2) for c in caches)
    slot = jnp.asarray([0, S, S - 1, 127, 128, S], jnp.int32)
    got = kv_row_insert(k_t, v_t, news[0][:, :, 0], news[1][:, :, 0], slot,
                        jnp.int32(1), interpret=True)
    mask = jnp.asarray((np.asarray(slot) < S)[:, None])
    want = _oracle(caches, news, 1, jnp.asarray(slot), mask)
    _assert_same([jnp.swapaxes(g, -1, -2) for g in got], want)
    assert rows_per_step(32, 2, 64, 2) == 32
    assert rows_per_step(12, 8, 128, 2) < 12


# --------------------------------------------------------------------------
# Whole streams, trace size, donation
# --------------------------------------------------------------------------

def _qwen2():
    cfg = get_config("qwen2-0.5b").reduced()
    return cfg, M.init_params(cfg, jax.random.key(0))


def _serve(cfg, params, max_len=256):
    rng = np.random.default_rng(11)
    # Prompts and outputs that cross 128-lane block edges.
    spec = [(5, 30), (120, 20), (33, 100), (70, 9), (140, 40), (2, 60)]
    reqs = [Request(tokens=rng.integers(0, cfg.vocab_size, (p,)).astype(
        np.int32), max_new_tokens=n) for p, n in spec]
    eng = ServeEngine(cfg, params, max_len=max_len, decode_window=4)
    return [np.asarray(r) for r in eng.serve(reqs, slots=3)]


def test_greedy_streams_match_the_whole_cache_rewrite(path, monkeypatch):
    cfg, params = _qwen2()
    got = _serve(cfg, params)
    monkeypatch.setattr(A, "writes_in_place", lambda cache: False)
    want = _serve(cfg, params)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)


def _eqns(jaxpr) -> int:
    """Equations of ``jaxpr`` and of every jaxpr nested in it (scan and
    cond bodies, inner jits, kernel bodies)."""
    n = 0
    for eqn in jaxpr.eqns:
        n += 1
        for p in eqn.params.values():
            for sub in p if isinstance(p, (list, tuple)) else (p,):
                if isinstance(sub, jcore.ClosedJaxpr):
                    n += _eqns(sub.jaxpr)
                elif isinstance(sub, jcore.Jaxpr):
                    n += _eqns(sub)
    return n


def _program_args(cfg, b, p=None):
    sds = jax.ShapeDtypeStruct
    state = M.abstract_decode_state(cfg, batch=b, max_len=S,
                                    insert_window=p or 4)
    vec, bvec = sds((b,), jnp.int32), sds((b,), jnp.bool_)
    cur, key = sds((b, 1), jnp.int32), sds((2,), jnp.uint32)
    params = M.abstract_params(cfg)
    if p is None:
        return (params, state, cur, vec, vec, vec, bvec, vec, key)
    return (params, state, sds((b, p), jnp.int32), bvec, vec, vec, vec, vec,
            vec, vec, bvec, cur, key)


def test_trace_size_does_not_grow_with_bucket_or_slots(path):
    """Set-up traces every admit bucket and the window in each process.
    An earlier attempt at this write was rejected for 17 s more
    ``setup_s`` on the chip, paid in every process, and a trace that grows
    with the prompt bucket (a loop over tokens or slots unrolled at trace
    time) is a cost of that kind.  The dense admit's jaxpr has as many
    equations at two buckets, and the window's at two slot counts."""
    cfg, _ = _qwen2()
    eng = ServeEngine(cfg, None, max_len=S, decode_window=4)
    admit = [_eqns(jax.make_jaxpr(eng._admit_step(p, 0.0, 0, None))(
        *_program_args(cfg, 4, p)).jaxpr) for p in (32, 160)]
    window = [_eqns(jax.make_jaxpr(eng._serve_window(4, 0.0, 0, None))(
        *_program_args(cfg, b)).jaxpr) for b in (2, 32)]
    assert admit[0] == admit[1], admit
    assert window[0] == window[1], window


def test_serve_programs_still_donate_their_state():
    from repro.analysis.donation import check_entry

    cfg, _ = _qwen2()
    entries = {e.name: e for e in E.audit_jit_entrypoints(cfg, max_len=S)}
    for name in ("serve.serve_window", "serve.admit"):
        findings = check_entry(entries[name])
        assert [f.severity.name for f in findings] == ["INFO"], findings
