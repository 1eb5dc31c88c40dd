"""The control: the plain reference in float8 put in the program's place.

On the chip (PERF.md) the control reads far above each cell's limit and
the program far below it.  Here the same runs go through ``correct`` at
a size a test run can hold (d 256, 4 layers, CPU), against limits for
that size set between the readings it gives there (program at most
0.050 and 0.012 sd, control 0.65-1.06 and 0.31-0.32 sd on the seeds
below): the program's run comes out correct and the control's does not.
"""

import json

import pytest

import bench_testlib
from bench import control

SIZES = {"rwkv6-1.6b": dict(num_layers=4, d_model=256, num_heads=4,
                            num_kv_heads=4, d_ff=1024, vocab_size=4096),
         "qwen2-0.5b": dict(num_layers=4, d_model=256, num_heads=4,
                            num_kv_heads=1, d_ff=1024, vocab_size=4096)}
#: ``max_gap_sd`` limits at the test's size.
TINY_LIMIT = {"rwkv6-1.6b.chat": 0.25, "qwen2-0.5b.long_decode": 0.08}


@pytest.mark.parametrize("cell", ["rwkv6-1.6b.chat", "qwen2-0.5b.long_decode"])
def test_control_stands_apart_from_the_program(tmp_path, monkeypatch, cell):
    arch = cell.rsplit(".", 1)[0]
    model = dict(bench_testlib.TINY_MODEL[arch], **SIZES[arch])
    monkeypatch.setitem(bench_testlib.TINY_MODEL, arch, model)
    root = bench_testlib.tiny_root(tmp_path, cell)
    mix_path = root / "bench" / "traffic" / f"{cell}.json"
    mix = json.loads(mix_path.read_text())
    mix["check"]["max_gap_sd"] = TINY_LIMIT[cell]
    mix_path.write_text(json.dumps(mix))
    seed = 2**31 + 11
    prog, ctl = control.readings(cell, [seed, seed + 1], {seed + 1}, 2.0,
                                 root=root, require_chip=False,
                                 log=lambda m: None)
    assert prog["correct"] is True, prog
    assert ctl["control_correct"] is False, ctl
    assert ctl["control_max_gap_sd"] > 3 * max(prog["max_gap_sd"],
                                               ctl["max_gap_sd"], 0.01), ctl
