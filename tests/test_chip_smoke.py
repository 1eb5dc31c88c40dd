"""``chip_smoke.py`` phases at reduced widths on the CPU.

The smoke itself runs on a TPU at published widths; here its phase
functions run on ``.reduced()`` configs with the WKV kernels forced into
interpret mode, so a broken phase shows up before any chip time is spent.
The four-chip phase runs on four virtual CPU devices in a subprocess (the
main test process stays one-device).
"""

from __future__ import annotations

import importlib.util
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest

import repro.kernels.wkv.ops as wkv_ops
from repro.configs.registry import get_config
from repro.serve.engine import RequestResult

ROOT = Path(__file__).resolve().parents[1]


def _load_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


cs = _load_smoke()


@pytest.fixture
def kernels_interpreted(monkeypatch):
    # Take the kernel routes the chip takes, interpreted on the CPU.
    monkeypatch.setattr(wkv_ops, "on_tpu", lambda: True)
    monkeypatch.setattr(wkv_ops, "interpret_default", lambda: True)


def test_rwkv_phase_reaches_both_wkv_routes(kernels_interpreted):
    cs.rwkv_phase(get_config("rwkv6-1.6b").reduced(),
                  short_lens=(20, 37, 52, 64), long_lens=(130, 260, 390, 500),
                  new_tokens=6, slots=4, decode_window=4, max_len=1024)


def test_wkv_kernel_check_passes(kernels_interpreted):
    cs.wkv_kernel_check(get_config("rwkv6-1.6b").reduced(), batch=2,
                        lengths=(128, 8), seed=0)


def test_gemma_phase_paged():
    cs.gemma_phase(get_config("gemma3-1b").reduced(), lens=(24, 90, 40),
                   new_tokens=4, slots=3, decode_window=4, max_len=256)


@pytest.mark.parametrize("outs, stats, match", [
    ([RequestResult(np.array([1, 2]), "deadline")], {}, "deadline"),
    ([RequestResult(np.array([1, 600]))], {}, "out of vocab"),
    ([RequestResult(np.array([1]))], {}, "budget"),
    ([RequestResult(np.array([1, 2]))], {"quarantines": 1}, "quarantines"),
])
def test_check_served_rejects(outs, stats, match):
    cfg = get_config("rwkv6-1.6b").reduced()
    reqs = cs.random_requests(cfg, [4], 2, seed=0)
    full = {"quarantines": 0, "recoveries": 0, "dispatch_retries": 0}
    full.update(stats)
    with pytest.raises(cs.SmokeFailure, match=match):
        cs.check_served(cfg, outs, full, reqs)


def test_main_refuses_cpu(capsys):
    assert cs.main([]) == 1
    out, err = capsys.readouterr()
    assert "no TPU" in err
    assert '"ok"' not in out


SCRIPT_4 = textwrap.dedent(
    """
    import dataclasses, os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    sys.path.insert(0, {root!r})
    import chip_smoke as cs
    from repro.configs.registry import get_config
    from repro.launch.mesh import make_debug_mesh

    # bf16, as published: the logits tolerance is bf16's rounding scale.
    cfg = dataclasses.replace(get_config("rwkv6-1.6b").reduced(),
                              dtype="bfloat16")
    cs.seqshard_phase(cfg, make_debug_mesh(data=1, model=4), batch=2,
                      prompt_len=1024, new_tokens=4)
    print("SEQSHARD_OK")
    """
)


def test_seqshard_phase_on_four_cpu_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(ROOT / "src"))
    env.pop("XLA_FLAGS", None)
    res = subprocess.run(
        [sys.executable, "-c", SCRIPT_4.format(root=str(ROOT))],
        capture_output=True, text=True, env=env, timeout=600)
    assert res.returncode == 0, f"stdout:\n{res.stdout}\nstderr:\n{res.stderr}"
    assert "SEQSHARD_OK" in res.stdout


def test_compile_cache_dir(monkeypatch):
    import jax

    from repro.launch import compile_cache

    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
        assert compile_cache.enable_compile_cache() == "/elsewhere/cache"
        assert jax.config.jax_compilation_cache_dir == was  # nothing set
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = compile_cache.enable_compile_cache()
        assert path == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
