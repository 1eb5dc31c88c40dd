"""The program's trace names: device scopes in the lowered serve programs,
and the host spans of ``ServeSession.step`` in a profiler trace.

The benchmark's trace reduction (``bench/scopes.py``) attributes device
time and idle gaps by exactly these names, so they are checked here by
name: a renamed or dropped scope fails a test, not a metric."""

import glob
import os
import re

import jax
import numpy as np
import pytest

from repro.configs.registry import get_config
from repro.model import model as M
from repro.serve import engine as E

#: Scopes in every admit and window program, whatever the model.
SERVE = ("serve.state_check", "serve.sample", "model.embed", "model.mlp",
         "model.head")
#: Scopes of each model's sequence mixer.
MIXER = {"rwkv6-1.6b": ("model.mixer.rwkv",),
         "qwen2-0.5b": ("model.mixer.attn", "model.attn.decode")}
#: Programs (by their donation-audit registration name) and the scopes
#: only they hold.
PROGRAMS = {"serve.admit": ("serve.reset_rows",),
            "serve.serve_window": (),
            "serve.paged_admit": ("serve.reset_rows",),
            "serve.paged_window": ()}


def _scopes_in(text: str, names) -> set:
    """The ``names`` that are a component of some op location's name
    stack in lowered text (``loc("jit(admit)/model.head/dot_general")``;
    inside a scan body the stack starts at the body)."""
    parts = {p for loc in re.findall(r'loc\("([^"]*)"', text)
             for p in loc.split("/")}
    return set(names) & parts


@pytest.fixture(scope="module", params=sorted(MIXER))
def lowered(request):
    cfg = get_config(request.param).reduced()
    entries = {e.name: e for e in E.audit_jit_entrypoints(cfg)}
    return request.param, {
        name: entries[name].fn.lower(*entries[name].args).as_text(
            debug_info=True)
        for name in PROGRAMS}


@pytest.mark.parametrize("program", sorted(PROGRAMS))
def test_programs_carry_their_scopes(lowered, program):
    arch, texts = lowered
    want = set(SERVE) | set(MIXER[arch]) | set(PROGRAMS[program])
    assert _scopes_in(texts[program], want) == want


def test_mixer_scope_follows_the_block_kind(lowered):
    arch, texts = lowered
    other = {s for a, ss in MIXER.items() if a != arch for s in ss}
    for text in texts.values():
        assert not _scopes_in(text, other)


def test_reset_rows_only_in_admits(lowered):
    _, texts = lowered
    assert not _scopes_in(texts["serve.serve_window"], ["serve.reset_rows"])


@pytest.mark.parametrize("max_len,dense_insert", [(128, True), (64, False)])
def test_insert_scope_marks_the_in_place_write(max_len, dense_insert):
    """``model.attn.insert`` is the in-place KV write of the dense admit
    and window: there where the cache's positions are a whole number of
    128 lanes, absent where the whole-cache rewrite stands in, in the
    paged programs, and in a model without a KV cache."""
    for arch in sorted(MIXER):
        cfg = get_config(arch).reduced()
        for e in E.audit_jit_entrypoints(cfg, max_len=max_len):
            if e.name not in PROGRAMS:
                continue
            text = e.fn.lower(*e.args).as_text(debug_info=True)
            want = (dense_insert and arch == "qwen2-0.5b"
                    and not e.name.startswith("serve.paged"))
            assert bool(_scopes_in(text, ["model.attn.insert"])) == want, (
                arch, e.name)


# --------------------------------------------------------------------------
# Host spans
# --------------------------------------------------------------------------

PHASES = (".prepare", ".dispatch", ".sync", ".bookkeep")


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """A small session served under the profiler on the CPU, with what it
    admitted recorded at the jit boundary."""
    from jax.profiler import ProfileData

    cfg = get_config("rwkv6-1.6b").reduced()
    eng = E.ServeEngine(cfg, M.init_params(cfg, jax.random.PRNGKey(0)),
                        max_len=128, decode_window=4)
    rng = np.random.default_rng(3)
    reqs = [E.Request(tokens=rng.integers(0, cfg.vocab_size, n),
                      max_new_tokens=m)
            for n, m in ((5, 6), (40, 3), (9, 9), (70, 2))]
    sess = E.ServeSession(eng, reqs, slots=2)
    admitted = []                # (rows, bucket, prompt tokens) per admit
    dispatch = eng._dispatch

    def spy(kind, fn, args, **kw):
        if kind == "admit":
            admitted.append((int(np.sum(args[3])), args[2].shape[1],
                             int(np.sum(args[4]))))
        return dispatch(kind, fn, args, **kw)

    eng._dispatch = spy
    sess.step()                                 # compiled outside the trace
    logdir = str(tmp_path_factory.mktemp("trace"))
    with jax.profiler.trace(logdir):
        while sess.busy:
            sess.step()
    sess.close()
    path = glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                                  "*.xplane.pb"))[0]
    spans = [(ev.name, ev.start_ns, ev.end_ns, dict(ev.stats))
             for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for ev in line.events
             if ev.name.startswith("serve.")]
    return sess, admitted[1:], sorted(spans, key=lambda s: s[1])


def _inside(child, parent) -> bool:
    return parent[1] <= child[1] and child[2] <= parent[2]


def test_steps_are_numbered_spans(traced):
    sess, _, spans = traced
    steps = [s for s in spans if s[0] == "serve.step"]
    assert [s[3]["step_num"] for s in steps] == list(range(1, sess.steps))


def test_admit_and_window_nest_under_a_step(traced):
    _, _, spans = traced
    steps = [s for s in spans if s[0] == "serve.step"]
    for sp in spans:
        if sp[0] != "serve.step":
            assert sum(_inside(sp, st) for st in steps) == 1, sp[0]


@pytest.mark.parametrize("parent", ["serve.admit", "serve.window"])
def test_phases_nest_in_order(traced, parent):
    _, _, spans = traced
    outer = [s for s in spans if s[0] == parent]
    assert outer
    for o in outer:
        kids = [s for s in spans if s[0].startswith(parent + ".")
                and _inside(s, o)]
        assert [k[0] for k in kids] == [parent + p for p in PHASES]
        assert all(a[2] <= b[1] for a, b in zip(kids, kids[1:]))


def test_admit_args_are_what_was_admitted(traced):
    sess, admitted, spans = traced
    args = [s[3] for s in spans if s[0] == "serve.admit"]
    assert [(a["rows"], a["bucket"], a["prompt_tokens"]) for a in args] \
        == admitted
    assert all(a["slots"] == sess.b == 2 for a in args)
    assert sum(a["rows"] for a in args) == 2      # the first admit is untraced


def test_window_args(traced):
    sess, _, spans = traced
    args = [s[3] for s in spans if s[0] == "serve.window"]
    assert args and all(a["k"] == sess.k_w == 4 for a in args)
    assert all(1 <= a["active"] <= sess.b for a in args)
