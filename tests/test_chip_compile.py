"""Every main-path Pallas kernel compiles for a TPU v5e at real widths.

Interpret mode runs the kernels on the CPU but cannot see what the TPU
compiler refuses: block shapes off the (8, 128) tiling, dynamic slices,
VMEM overruns.  These tests compile each kernel for one chip of a
described, unattached ``v5e:2x2`` topology (nothing runs) and check that
the compiled program holds the kernel (``tpu_custom_call``).

Shapes are the published widths the serve and train paths use:
``rwkv6-1.6b`` WKV (H 32, Dh 64, bf16), ``recurrentgemma-2b`` RG-LRU and
temporal conv (d_rnn 2560), ``gemma3-1b`` attention (4 x 256 heads, one
KV head, window 1024), ``qwen2-0.5b`` KV row insert (24 layers, 32
slots, 2 KV heads of 64, 2048 positions).  The ``qwen2-0.5b`` serve
window and admit compile whole for one chip, the row kernel in the
window, both with their decode state aliased in place.

The mesh cases compile the serve engine's jits for all four chips of the
topology: ``rwkv6-1.6b`` at its widths with the depth cut to two layers,
the kernels forced on as on a TPU.  The compiler cannot partition a
Pallas kernel, so a kernel outside a ``shard_map`` fails there.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and test workers
import every test file.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

import repro.kernels.kv_insert.ops as kv_ops
import repro.kernels.wkv.ops as wkv_ops
import repro.model.recurrent as rec
from repro.configs.registry import get_config

from repro.kernels.elevator_scan.decode import elevator_decode_window_pallas
from repro.kernels.elevator_scan.kernel import elevator_scan_pallas
from repro.kernels.kv_insert.kernel import kv_row_insert
from repro.kernels.local_attention.kernel import flash_attention_pallas
from repro.kernels.token_shift.kernel import token_shift_pallas
from repro.kernels.wkv.bwd import wkv_pallas_bwd
from repro.kernels.wkv.decode import wkv_decode_pallas, wkv_decode_window_pallas
from repro.kernels.wkv.kernel import (
    wkv_pallas,
    wkv_pallas_summary,
    wkv_pallas_train,
    wkv_pallas_train_summary,
)
from repro.launch.roofline import parse_collective_bytes
from repro.model import model as M
from repro.serve import engine as serve_engine
from repro.serve.engine import ServeEngine

B, T = 4, 2048
H, DH = 32, 64                    # rwkv6-1.6b WKV heads
D_RNN = 2560                      # recurrentgemma-2b recurrence width
BF16, F32 = jnp.bfloat16, jnp.float32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def four_chips(topo):
    return Mesh(np.array(topo.devices).reshape(1, 4), ("data", "model"))


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep the cache off here."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _wkv(s, t, chunk=None, kernel=wkv_pallas):
    seq = s((B, H, t, DH), BF16)
    args = (seq, seq, seq, seq, s((H, DH), BF16), s((B, H, DH, DH), F32))
    if chunk is None:
        return jax.jit(kernel), args
    return jax.jit(lambda *a: kernel(*a, chunk=chunk)), args


def _wkv_bwd(s, chunk):
    seq = s((B, H, T, DH), BF16)
    state = s((B, H, DH, DH), F32)
    return (jax.jit(lambda *a: wkv_pallas_bwd(*a, chunk=chunk)),
            (seq, seq, seq, seq, s((H, DH), BF16),
             s((B, H, T // chunk, DH, DH), F32), seq, state))


def _elevator(s, chunk):
    x = s((B, T, D_RNN), F32)
    return (jax.jit(lambda a, x_, h: elevator_scan_pallas(a, x_, h,
                                                          chunk=chunk)),
            (x, x, s((B, D_RNN), F32)))


def _elevator_decode(s, k):
    x = s((B, k, D_RNN), F32)
    return jax.jit(elevator_decode_window_pallas), (x, x, s((B, D_RNN), F32))


def _token_shift(s, t):
    return (jax.jit(lambda x, w: token_shift_pallas(x, w, chunk=256)),
            (s((B, t, D_RNN), BF16), s((4, D_RNN), BF16)))


def _flash(s):
    kv = s((B, 1, T, 256), BF16)
    return (jax.jit(lambda q, k, v: flash_attention_pallas(
                q, k, v, causal=True, window=1024)),
            (s((B, 4, T, 256), BF16), kv, kv))


def _kv_row(s):
    cache = s((24, 32, 2, 64, 2048), BF16)   # (L, B, Hkv, Dh, S)
    row = s((32, 2, 64), BF16)
    return (jax.jit(kv_row_insert, donate_argnums=(0, 1)),
            (cache, cache, row, row, s((32,), jnp.int32), s((), jnp.int32)))


#: name -> builder(sds) -> (jitted fn, abstract args).  ``sds(shape,
#: dtype)`` places a ShapeDtypeStruct on the described chip.
CASES = {
    # WKV chunked sweeps: chunk 16 is the model's, 64 the kernel default.
    "wkv.fwd.c16": lambda s: _wkv(s, T, 16),
    "wkv.fwd.c64": lambda s: _wkv(s, T, 64),
    "wkv.train": lambda s: _wkv(s, T, 16, wkv_pallas_train),
    "wkv.summary": lambda s: _wkv(s, T // 4, 16, wkv_pallas_summary),
    "wkv.train_summary": lambda s: _wkv(s, T // 4, 16,
                                        wkv_pallas_train_summary),
    "wkv.bwd": lambda s: _wkv_bwd(s, 16),
    # WKV decode: one token, a serve window, an admission window.
    "wkv.decode": lambda s: _wkv(s, 1, kernel=wkv_decode_pallas),
    "wkv.decode_window.k8": lambda s: _wkv(
        s, 8, kernel=wkv_decode_window_pallas),
    "wkv.decode_window.k64": lambda s: _wkv(
        s, 64, kernel=wkv_decode_window_pallas),
    "elevator.fwd": lambda s: _elevator(s, 256),
    "elevator.decode_window.k1": lambda s: _elevator_decode(s, 1),
    "elevator.decode_window.k8": lambda s: _elevator_decode(s, 8),
    # Temporal conv: a prefill sweep and the decode shape (3-row tail + 1).
    "token_shift.t2048": lambda s: _token_shift(s, T),
    "token_shift.t4": lambda s: _token_shift(s, 4),
    "local_attention.flash": _flash,
    "kv_insert.row": _kv_row,
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(name, one_chip, no_compile_cache):
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    fn, args = CASES[name](sds)
    text = fn.lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, f"{name}: no kernel in the program"


@pytest.fixture
def tpu_dispatch(monkeypatch):
    """The model's WKV dispatch as on a TPU: compiled kernels, no jnp
    path, no interpreter."""
    monkeypatch.setattr(rec, "on_tpu", lambda: True)
    monkeypatch.setattr(wkv_ops, "on_tpu", lambda: True)
    monkeypatch.setattr(wkv_ops, "interpret_default", lambda: False)


MESH_B, MESH_T, MESH_K = 2, 4096, 8


@pytest.mark.parametrize("jit", ["decode_window", "seq_prefill_admit"])
def test_mesh_serve_compiles_for_four_v5e(jit, four_chips, tpu_dispatch,
                                          no_compile_cache):
    cfg = dataclasses.replace(get_config("rwkv6-1.6b"), num_layers=2)
    rep = NamedSharding(four_chips, P())

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=rep)

    def place(tree):
        return jax.tree.map(lambda a: sds(a.shape, a.dtype), tree)

    b, max_len = MESH_B, MESH_T + MESH_K
    params = place(M.abstract_params(cfg))
    state = place(M.abstract_decode_state(cfg, batch=b, max_len=max_len,
                                          insert_window=MESH_T))
    eng = ServeEngine(cfg, None, max_len=max_len, decode_window=MESH_K,
                      mesh=four_chips)
    vec, bvec = sds((b,), jnp.int32), sds((b,), jnp.bool_)
    cur, key = sds((b, 1), jnp.int32), sds((2,), jnp.uint32)
    if jit == "decode_window":
        fn = eng._serve_window(MESH_K, 0.0, 0, None)
        args = (params, state, cur, vec, vec, vec, bvec, vec, key)
    else:
        fn = eng._admit_step(MESH_T, 0.0, 0, None)
        args = (params, state, sds((b, MESH_T), jnp.int32), bvec, vec, vec,
                vec, vec, vec, vec, bvec, cur, key)
    # The engine's own wrapper installs its mesh rules while the jit
    # traces; the outer jit only makes it lowerable from shapes.
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, f"{jit}: no kernel in the program"
    if jit == "seq_prefill_admit":
        coll = parse_collective_bytes(text)
        assert coll["collective-permute"]["count"], coll
        assert not coll["all-gather"]["count"], coll


@pytest.mark.parametrize("jit", ["serve.serve_window", "serve.admit"])
def test_dense_serve_programs_compile_with_the_row_insert(
        jit, one_chip, monkeypatch, no_compile_cache):
    """The ``qwen2-0.5b`` window and admit at their widths for one chip:
    the window holds the row kernel, and the donation pass finds both
    aliasing their decode state."""
    from repro.analysis.donation import check_entry

    monkeypatch.setattr(kv_ops, "on_tpu", lambda: True)
    monkeypatch.setattr(kv_ops, "interpret_default", lambda: False)
    cfg = get_config("qwen2-0.5b")
    entry = {e.name: e for e in serve_engine.audit_jit_entrypoints(
        cfg, batch=32, max_len=2048, decode_window=16, prompt=128)}[jit]
    args = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        entry.args)
    entry = dataclasses.replace(entry, args=args)
    findings = check_entry(entry)
    assert [f.severity.name for f in findings] == ["INFO"], findings
    if jit == "serve.serve_window":
        text = entry.fn.lower(*args).compile().as_text()
        assert "kv_row_insert" in text, "no row kernel in the window"
