"""Multi-device tests for ICI-level elevator primitives.

The main pytest process must see exactly 1 CPU device (the dry-run alone may
spawn 512), so these tests re-invoke python in a subprocess with
``--xla_force_host_platform_device_count=8`` and assert inside it.
(The tier-1 lane 2 in scripts/tier1.sh additionally runs the in-process
device-gated tests with 8 fake devices.)

SCRIPT exercises the core primitives; SCRIPT_WKV is the
sequence-parallel WKV acceptance suite — forward and gradient parity of
``wkv_seqshard`` against the single-device fused path on 8 devices, a
jaxpr audit proving only O(Dh²) segment summaries (never token
activations) cross the ``seq`` axis — via the shared
``repro.analysis.collectives`` pass, which replaced the walker that used
to live inline here — the model-level ``prefill_seq`` dispatch and the
serve-engine long-context prefill step.  SCRIPT_WKV_HEADS checks the
head-sharded WKV kernel path that decode windows take under a mesh.
"""

import subprocess
import sys
import textwrap
from pathlib import Path

SRC = str(Path(__file__).resolve().parents[1] / "src")

SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    from repro.core import (
        DIAG_STATE, device_shift, halo_exchange, ring_pass, seq_carry_scan,
        device_linear_scan_carry, linear_scan, pipeline_apply,
    )

    assert len(jax.devices()) == 8, jax.devices()
    mesh = Mesh(np.array(jax.devices()), ("x",))

    # --- device_shift: elevator across shards -------------------------------
    x = jnp.arange(8.0)  # one element per shard
    out = shard_map(lambda v: device_shift(v, "x", 1, fill=-1.0),
                    mesh=mesh, in_specs=P("x"), out_specs=P("x"))(x)
    np.testing.assert_array_equal(out, [-1, 0, 1, 2, 3, 4, 5, 6])

    out = shard_map(lambda v: device_shift(v, "x", -2, fill=9.0),
                    mesh=mesh, in_specs=P("x"), out_specs=P("x"))(x)
    np.testing.assert_array_equal(out, [2, 3, 4, 5, 6, 7, 9, 9])

    # --- ring_pass -----------------------------------------------------------
    out = shard_map(lambda v: ring_pass(v, "x", 1),
                    mesh=mesh, in_specs=P("x"), out_specs=P("x"))(x)
    np.testing.assert_array_equal(out, [7, 0, 1, 2, 3, 4, 5, 6])

    # --- halo_exchange: local-attention K/V neighborhoods --------------------
    seq = jnp.arange(32.0)   # 4 tokens per shard
    def halo_fn(v):
        h = halo_exchange(v, "x", left=2, right=1, fill=0.0)
        return h.reshape(1, -1)  # (1, 7) per shard -> stacked over shards
    out = shard_map(halo_fn, mesh=mesh, in_specs=P("x"), out_specs=P("x", None))(seq)
    # Shard 1 holds tokens [4..7]; halo = last 2 of shard 0 + first 1 of shard 2.
    np.testing.assert_array_equal(out[1], [2, 3, 4, 5, 6, 7, 8])
    # Shard 0 has no left producer -> elevator constant 0.
    np.testing.assert_array_equal(out[0], [0, 0, 0, 1, 2, 3, 4])

    # --- device_linear_scan_carry: cross-shard recurrence carries ------------
    T, D = 32, 3
    rng = np.random.default_rng(0)
    a = rng.uniform(0.6, 1.0, (T, D)).astype(np.float32)
    b = rng.standard_normal((T, D)).astype(np.float32)

    def chunk_scan_sharded(a_loc, b_loc):
        h_loc = linear_scan(a_loc, b_loc)          # local inclusive scan
        a_seg = jnp.prod(a_loc, axis=0)
        b_seg = h_loc[-1]
        ca, cb = device_linear_scan_carry(a_seg, b_seg, "x")
        # entering state = ca * h0 + cb with h0 = 0 -> cb
        a_cum = jnp.cumprod(a_loc, axis=0)
        return h_loc + a_cum * cb[None]

    out = shard_map(chunk_scan_sharded, mesh=mesh,
                    in_specs=(P("x"), P("x")), out_specs=P("x"))(
        jnp.asarray(a), jnp.asarray(b))
    ref = np.zeros_like(b)
    prev = np.zeros(D, np.float32)
    for t in range(T):
        prev = a[t] * prev + b[t]
        ref[t] = prev
    np.testing.assert_allclose(np.asarray(out), ref, rtol=3e-4, atol=3e-4)

    # --- nonzero h0 entering shard 0 (the elevator boundary constant) --------
    h0 = rng.standard_normal(D).astype(np.float32)

    def chunk_scan_h0(a_loc, b_loc):
        h_loc = linear_scan(a_loc, b_loc)
        ca, cb = device_linear_scan_carry(
            jnp.prod(a_loc, axis=0), h_loc[-1], "x")
        enter = ca * h0 + cb
        return h_loc + jnp.cumprod(a_loc, axis=0) * enter[None]

    out = shard_map(chunk_scan_h0, mesh=mesh,
                    in_specs=(P("x"), P("x")), out_specs=P("x"))(
        jnp.asarray(a), jnp.asarray(b))
    ref = np.zeros_like(b)
    prev = h0.copy()
    for t in range(T):
        prev = a[t] * prev + b[t]
        ref[t] = prev
    np.testing.assert_allclose(np.asarray(out), ref, rtol=3e-4, atol=3e-4)

    # --- reverse sweeps: the device-space reverse elevator -------------------
    A = rng.uniform(0.5, 1.0, (8, D)).astype(np.float32)
    B = rng.standard_normal((8, D)).astype(np.float32)

    def rev_carry(a_, b_):
        ca, cb = device_linear_scan_carry(a_[0], b_[0], "x", reverse=True)
        return ca[None], cb[None]

    ca, cb = shard_map(rev_carry, mesh=mesh,
                       in_specs=(P("x", None), P("x", None)),
                       out_specs=(P("x", None), P("x", None)))(
        jnp.asarray(A), jnp.asarray(B))
    prev_a = np.ones(D, np.float32)
    prev_b = np.zeros(D, np.float32)
    for i in range(7, -1, -1):
        np.testing.assert_allclose(np.asarray(ca[i]), prev_a, rtol=1e-5)
        np.testing.assert_allclose(np.asarray(cb[i]), prev_b, rtol=1e-5,
                                   atol=1e-5)
        prev_a = A[i] * prev_a
        prev_b = A[i] * prev_b + B[i]

    # --- DIAG_STATE monoid across devices: matrix state, diag decay ----------
    dh = 3
    Am = rng.uniform(0.5, 1.0, (8, dh)).astype(np.float32)
    Bm = rng.standard_normal((8, dh, dh)).astype(np.float32)
    h0m = rng.standard_normal((dh, dh)).astype(np.float32)

    def mat_carry(a_, b_):
        ca, cb = device_linear_scan_carry(a_[0], b_[0], "x",
                                          monoid=DIAG_STATE)
        return ca[None], cb[None]

    ca, cb = shard_map(mat_carry, mesh=mesh,
                       in_specs=(P("x", None), P("x", None, None)),
                       out_specs=(P("x", None), P("x", None, None)))(
        jnp.asarray(Am), jnp.asarray(Bm))
    prev = h0m.copy()
    for i in range(8):
        enter = np.asarray(ca[i])[:, None] * h0m + np.asarray(cb[i])
        np.testing.assert_allclose(enter, prev, rtol=1e-4, atol=1e-4)
        prev = Am[i][:, None] * prev + Bm[i]

    # --- seq_carry_scan: sequential chain across shards ----------------------
    vals = jnp.arange(1.0, 9.0)  # one per shard
    def chunk_fn(carry, v):
        s = carry + v.sum()
        return s, jnp.zeros_like(v) + s
    def run_seq(v):
        c, y = seq_carry_scan(chunk_fn, jnp.asarray(0.0), v, "x")
        return c.reshape(1), y  # per-shard carry, stacked over shards
    carry, ys = shard_map(
        run_seq, mesh=mesh, in_specs=P("x"), out_specs=(P("x"), P("x")))(vals)
    np.testing.assert_allclose(np.asarray(ys), np.cumsum(np.arange(1.0, 9.0)), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(carry)[-1], 36.0, rtol=1e-6)

    # --- seq_carry_scan reverse: the chain runs last-shard -> first ----------
    def run_seq_rev(v):
        c, y = seq_carry_scan(chunk_fn, jnp.asarray(0.0), v, "x",
                              reverse=True)
        return c.reshape(1), y
    carry, ys = shard_map(
        run_seq_rev, mesh=mesh, in_specs=P("x"), out_specs=(P("x"), P("x")))(vals)
    want = np.cumsum(np.arange(1.0, 9.0)[::-1])[::-1]
    np.testing.assert_allclose(np.asarray(ys), want, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(carry)[0], 36.0, rtol=1e-6)

    # --- pipeline_apply: 8-stage pipeline == composed function ---------------
    n_micro, mb, d = 5, 2, 4
    xs = jnp.asarray(rng.standard_normal((n_micro, mb, d)).astype(np.float32))
    w = jnp.asarray(rng.standard_normal((8, d, d)).astype(np.float32) * 0.3)

    def stage_fn(wi, x):
        return jnp.tanh(x @ wi)

    def run(w_all, x_all):
        out = pipeline_apply(stage_fn, w_all[0], x_all, "x")
        # Result is valid on the last stage; broadcast it.
        last = jax.lax.axis_index("x") == 7
        return jax.lax.psum(jnp.where(last, out, 0.0), "x")

    out = shard_map(run, mesh=mesh, in_specs=(P("x"), P()), out_specs=P())(w, xs)
    ref = np.asarray(xs)
    for i in range(8):
        ref = np.tanh(ref @ np.asarray(w[i]))
    np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-4, atol=2e-4)

    print("MULTIDEVICE_OK")
    """
)


SCRIPT_WKV = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import types
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh

    from repro.kernels.wkv.ops import wkv_fused
    from repro.kernels.wkv.seqpar import wkv_seqshard

    assert len(jax.devices()) == 8, jax.devices()
    mesh = Mesh(np.array(jax.devices()), ("seq",))

    b, h, t, dh = 2, 2, 128, 8
    rng = np.random.default_rng(0)
    r = jnp.asarray(rng.standard_normal((b, h, t, dh)).astype(np.float32))
    k = jnp.asarray(rng.standard_normal((b, h, t, dh)).astype(np.float32))
    v = jnp.asarray(rng.standard_normal((b, h, t, dh)).astype(np.float32))
    w = jnp.asarray(rng.uniform(0.85, 0.999, (b, h, t, dh)).astype(np.float32))
    u = jnp.asarray(rng.standard_normal((h, dh)).astype(np.float32))
    h0 = jnp.asarray(rng.standard_normal((b, h, dh, dh)).astype(np.float32))

    def shard(*args):
        return wkv_seqshard(*args, mesh=mesh, seq_axis="seq", chunk=8,
                            use_kernel=False)
    def single(*args):
        return wkv_fused(*args, chunk=8, use_kernel=False)

    # --- forward parity on 8 devices, nonzero h0 -----------------------------
    out1, s1 = single(r, k, v, w, u, h0)
    out2, s2 = shard(r, k, v, w, u, h0)
    np.testing.assert_allclose(np.asarray(out2), np.asarray(out1),
                               rtol=3e-4, atol=3e-4)
    np.testing.assert_allclose(np.asarray(s2), np.asarray(s1),
                               rtol=3e-4, atol=3e-4)

    # --- gradient parity: the custom VJP composes with the device sweep ------
    co = jnp.asarray(rng.standard_normal((b, h, t, dh)).astype(np.float32))
    cs = jnp.asarray(rng.standard_normal((b, h, dh, dh)).astype(np.float32))

    def loss(fn):
        def f(*args):
            o, s = fn(*args)
            return (o * co).sum() + (s * cs).sum()
        return f

    g1 = jax.grad(loss(single), argnums=tuple(range(6)))(r, k, v, w, u, h0)
    g2 = jax.grad(loss(shard), argnums=tuple(range(6)))(r, k, v, w, u, h0)
    for name, a_, b_ in zip("r k v w u h0".split(), g1, g2):
        np.testing.assert_allclose(np.asarray(b_), np.asarray(a_),
                                   rtol=3e-3, atol=3e-3, err_msg=name)

    # --- jaxpr audit: only segment summaries cross the seq axis --------------
    # Every collective over the mesh (ppermute hops of the carry, the final
    # masked psum) must move O(Dh^2) summaries; a token-sized operand
    # (B, H, T/n, Dh) would mean the protocol regressed to a gather.  The
    # walker that used to live inline here is now the shared static-audit
    # pass (repro.analysis.collectives) — same budget, same gather ban.
    from repro.analysis.collectives import audit_collectives, has_reverse_hops
    from repro.analysis.findings import errors, format_table

    summary_size = b * h * dh * dh          # the (Dh, Dh) state summary

    fwd_jaxpr = jax.make_jaxpr(shard)(r, k, v, w, u, h0)
    bwd_jaxpr = jax.make_jaxpr(
        jax.grad(loss(shard), argnums=tuple(range(6))))(r, k, v, w, u, h0)
    for what, closed in (("forward", fwd_jaxpr), ("backward", bwd_jaxpr)):
        bad = errors(audit_collectives(
            closed, axis="seq", max_elements=summary_size, what=what))
        assert not bad, format_table(bad)
    # The transposed carry is the device-space *reverse* elevator: the
    # backward must contain ppermute hops running high->low shard index.
    assert has_reverse_hops(bwd_jaxpr, "seq"), (
        "backward jaxpr has no reverse-direction ppermute hops")

    # --- model level: apply_rwkv_block under prefill_seq rules ---------------
    from repro.model import recurrent as rec
    from repro.model.sharding import make_rules, sharding_context

    mesh2 = Mesh(np.array(jax.devices()).reshape(2, 4), ("data", "model"))
    rules = make_rules(mesh2, "prefill_seq")
    assert rules["seq"] == "model", rules

    d = 128
    mk = lambda shape, scale=0.1: jnp.asarray(
        rng.standard_normal(shape).astype(np.float32) * scale)
    params = {
        "mu": mk((5, d)),
        "w_r": mk((d, d)), "w_k": mk((d, d)),
        "w_v": mk((d, d)), "w_g": mk((d, d)),
        "w_decay_base": mk((d,)),
        "w_decay_lora_a": mk((d, 64)),
        "w_decay_lora_b": mk((64, d)),
        "u_bonus": mk((d,)),
        "w_o": mk((d, d)),
        "out_norm": {"scale": jnp.ones((d,), jnp.float32)},
    }
    cfg = types.SimpleNamespace(fsdp_gather_weights=False, norm_eps=1e-6)
    x = mk((2, 64, d), scale=1.0)

    out_plain, _ = rec.apply_rwkv_block(params, x, cfg, chunk=16)
    with mesh2, sharding_context(mesh2, rules):
        out_seq, _ = rec.apply_rwkv_block(params, x, cfg, chunk=16)
    np.testing.assert_allclose(np.asarray(out_seq), np.asarray(out_plain),
                               rtol=3e-4, atol=3e-4)

    def block_loss(p, x_, seq):
        if seq:
            with mesh2, sharding_context(mesh2, rules):
                out, _ = rec.apply_rwkv_block(p, x_, cfg, chunk=16)
        else:
            out, _ = rec.apply_rwkv_block(p, x_, cfg, chunk=16)
        return (out * out).sum()

    gp = jax.grad(block_loss)(params, x, False)
    gs = jax.grad(block_loss)(params, x, True)
    err = jax.tree.map(
        lambda a_, b_: float(np.max(np.abs(np.asarray(a_) - np.asarray(b_)))),
        gp, gs)
    worst = max(jax.tree.leaves(err))
    assert worst < 5e-3, err

    # --- serve engine: long-context prefill takes the seq-parallel rules -----
    from repro.configs.registry import get_config
    from repro.model import model as M
    from repro.serve.engine import make_prefill_step, make_seq_prefill_step

    cfg_m = get_config("rwkv6-1.6b").reduced()
    params_m = M.init_params(cfg_m, jax.random.key(0))
    tokens = jnp.asarray(
        rng.integers(0, cfg_m.vocab_size, (2, 64)), jnp.int32)
    plain = make_prefill_step(cfg_m)(params_m, tokens)
    seqp = make_seq_prefill_step(cfg_m, mesh2, min_len=32)(params_m, tokens)
    np.testing.assert_allclose(np.asarray(seqp), np.asarray(plain),
                               rtol=2e-3, atol=2e-3)
    # Short prompts stay on the plain rules (no seq sharding below min_len).
    short = jnp.asarray(rng.integers(0, cfg_m.vocab_size, (2, 16)), jnp.int32)
    seqp_short = make_seq_prefill_step(cfg_m, mesh2, min_len=32)(
        params_m, short)
    plain_short = make_prefill_step(cfg_m)(params_m, short)
    np.testing.assert_allclose(np.asarray(seqp_short),
                               np.asarray(plain_short), rtol=2e-3, atol=2e-3)

    print("MULTIDEVICE_WKV_OK")
    """
)


SCRIPT_WKV_HEADS = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import types
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh

    from repro.model import recurrent as rec
    from repro.model.sharding import make_rules, sharding_context

    # Decode windows of a mesh engine: the WKV kernel (interpreted here)
    # runs per (batch, head) block inside a shard_map, which is what lets
    # the TPU compiler take it.  Same numbers as the kernel on one device.
    mesh = Mesh(np.array(jax.devices()).reshape(2, 4), ("data", "model"))
    rules = make_rules(mesh, "decode")
    b, d, t = 2, 256, 8
    h = d // rec.RWKV_HEAD_DIM
    with sharding_context(mesh, rules):
        assert rec._wkv_head_plan(b, h, True) == (mesh, "data", "model")
        assert rec._wkv_head_plan(b, h, False) is None
        assert rec._wkv_head_plan(b, 3, True) == (mesh, "data", None)
    assert rec._wkv_head_plan(b, h, True) is None

    rng = np.random.default_rng(0)
    mk = lambda shape, scale=0.1: jnp.asarray(
        rng.standard_normal(shape).astype(np.float32) * scale)
    params = {
        "mu": mk((5, d)),
        "w_r": mk((d, d)), "w_k": mk((d, d)),
        "w_v": mk((d, d)), "w_g": mk((d, d)),
        "w_decay_base": mk((d,)),
        "w_decay_lora_a": mk((d, 64)),
        "w_decay_lora_b": mk((64, d)),
        "u_bonus": mk((d,)),
        "w_o": mk((d, d)),
        "out_norm": {"scale": jnp.ones((d,), jnp.float32)},
    }
    cfg = types.SimpleNamespace(fsdp_gather_weights=False, norm_eps=1e-6)
    state = rec.RecState(h=mk((b, h, 64, 64), 1.0), conv=mk((b, 1, d), 1.0))
    x = mk((b, t, d), scale=1.0)

    def block(x_, st):
        return rec.apply_rwkv_block(params, x_, cfg, state=st, use_kernel=True)

    out1, st1 = jax.jit(block)(x, state)
    with mesh, sharding_context(mesh, rules):
        fn = jax.jit(block)
        assert "shard_map" in str(jax.make_jaxpr(fn)(x, state))
        out4, st4 = fn(x, state)
    np.testing.assert_allclose(np.asarray(out4), np.asarray(out1),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(st4.h), np.asarray(st1.h),
                               rtol=1e-5, atol=1e-5)
    print("MULTIDEVICE_WKV_HEADS_OK")
    """
)


def _run(script: str) -> subprocess.CompletedProcess:
    return subprocess.run(
        [sys.executable, "-c", script],
        capture_output=True,
        text=True,
        env={"PYTHONPATH": SRC, "PATH": "/usr/bin:/bin:/usr/local/bin", "HOME": "/root",
             # The script forces host-platform devices; skip TPU probing
             # (30-retry metadata fetches) in containers with libtpu baked in.
             "JAX_PLATFORMS": "cpu"},
        timeout=600,
    )


def test_multidevice_primitives():
    res = _run(SCRIPT)
    assert res.returncode == 0, f"stdout:\n{res.stdout}\nstderr:\n{res.stderr}"
    assert "MULTIDEVICE_OK" in res.stdout


def test_multidevice_wkv_seqshard():
    res = _run(SCRIPT_WKV)
    assert res.returncode == 0, f"stdout:\n{res.stdout}\nstderr:\n{res.stderr}"
    assert "MULTIDEVICE_WKV_OK" in res.stdout


def test_multidevice_wkv_headshard():
    res = _run(SCRIPT_WKV_HEADS)
    assert res.returncode == 0, f"stdout:\n{res.stdout}\nstderr:\n{res.stderr}"
    assert "MULTIDEVICE_WKV_HEADS_OK" in res.stdout
