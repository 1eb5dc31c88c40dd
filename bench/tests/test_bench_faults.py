"""With the timed path broken underneath, a run's ``correct`` comes out
false; unbroken, it comes out true.  Each fault is planted in the
engine's compiled admit or decode-window program, the rest of the run
(the open loop, the drain, the sample, the reference) as on the chip,
at a tiny size on the CPU."""

import jax
import jax.numpy as jnp
import pytest

import bench_testlib  # noqa: F401
from bench_testlib import run_tiny
from repro.serve import engine as E

CELLS = ["rwkv6-1.6b.chat", "qwen2-0.5b.long_decode"]


def _wrap(monkeypatch, method, change):
    orig = getattr(E.ServeEngine, method)

    def patched(self, *a, **kw):
        fn = orig(self, *a, **kw)
        return lambda *args: change(self, fn, args)

    monkeypatch.setattr(E.ServeEngine, method, patched)


def token_altered(self, fn, args):
    """Every slot's first token of each window comes out one id higher."""
    out = fn(*args)
    toks = out[6].at[0].set((out[6][0] + 1) % self.cfg.vocab_size)
    return out[:6] + (toks,) + out[7:]


def state_unchanged(self, fn, args):
    """The admission hands back the state it was given: the prompt never
    enters the cache or the recurrent state."""
    kept = jax.tree.map(jnp.copy, args[1])
    out = fn(*args)
    return (kept,) + out[1:]


def half_batch(self, fn, args):
    """The admission prefills half of the requests (those with even ids)
    and leaves the other half out: their rows keep a stale state."""
    row, req_ids = args[3], args[9]
    return fn(*args[:3], row & (req_ids % 2 == 0), *args[4:])


FAULTS = {"token_altered": ("_serve_window", token_altered),
          "state_unchanged": ("_admit_step", state_unchanged),
          "half_batch": ("_admit_step", half_batch)}


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(tmp_path, cell):
    r = run_tiny(tmp_path, cell)
    assert r["correct"], r["checks"]


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("cell", CELLS)
def test_fault_is_not_correct(tmp_path, monkeypatch, cell, fault):
    method, change = FAULTS[fault]
    _wrap(monkeypatch, method, change)
    r = run_tiny(tmp_path, cell)
    assert not r["correct"]
    gap = r["checks"]["max_gap_sd"]
    assert gap["value"] > gap["limit"], r["checks"]
