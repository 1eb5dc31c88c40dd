"""Everything a cell needs is found by name; BENCHMARK.json keeps its
contract; the result line has its schema; an unknown chip is an error."""

import json
import re
import shutil

import pytest

import bench_testlib  # noqa: F401
from bench import harness
from bench_testlib import REPO, load_json, run_tiny

BENCH = load_json(REPO / "BENCHMARK.json")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_benchmark_json_contract():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["bench"]
    # The length every bound and limit in PERF.md was measured at.
    assert BENCH["run_seconds"] == 51
    names = [c["name"] for c in BENCH["configs"]]
    assert len(set(names)) == len(names)
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("bench/") and (REPO / c["file"]).exists()
    cells = [w["name"] for w in BENCH["workloads"]]
    assert len(set(cells)) == len(cells)
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(set(pairs)) == len(pairs)
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["config"] in names and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200
    assert {m["name"] for m in BENCH["end_to_end"]} >= {"setup_s"}
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(m["name"]) and m["better"] in ("lower", "higher")
        for cell in m.get("workloads", []):
            assert cell in cells
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        moved = e2e[m["moves"]]
        for cell in m["workloads"]:
            assert cell in moved.get("workloads", cells)
        if m["name"].split(".")[0].endswith("_roofline"):
            assert m["unit"] == "%"
    for cell in cells:
        assert any(cell in m.get("workloads", cells) for m in BENCH["per_layer"])
        assert sum(cell in m.get("workloads", cells)
                   for m in BENCH["end_to_end"]) >= 2
    assert len(json.dumps(BENCH)) < 64 * 1024


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_found_by_name(cell):
    bench, c = harness.find_cell(REPO, cell)
    assert c.mix["config"] == c.entry["config"]
    assert c.config["name"] == c.entry["config"]
    assert hasattr(c.reference(), "hidden") and hasattr(c.reference(), "head")
    cfg = harness.arch_config(c.config)
    for key, value in c.config["model"].items():
        assert getattr(cfg, key) == value
    for trace in (False, True):
        for entry, reader in c.metrics(bench, trace):
            assert callable(reader.read)


@pytest.mark.parametrize("metric", BENCH["end_to_end"] + BENCH["per_layer"],
                         ids=lambda m: m["name"])
def test_every_metric_finds_its_reader(metric):
    """BENCHMARK.json alone declares a metric; its reader only reads."""
    path = harness.reader_path(REPO, metric["name"])
    assert path.exists(), path
    assert path.name in (f"{metric['name']}.py",
                         f"{metric['name'].split('.')[0]}.py")
    r = harness.load_module(path)
    assert callable(r.read)
    for key in ("LAYER", "UNIT", "SOURCE", "MOVES", "WORKLOADS"):
        assert not hasattr(r, key)


def test_new_cell_config_and_metric_are_only_files(tmp_path):
    """A later PR adds a configuration, a mix and a metric as new files and
    new BENCHMARK.json entries; the harness finds them unchanged."""
    root = tmp_path
    shutil.copytree(REPO / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    cfg = load_json(REPO / "bench" / "configs" / "qwen2-0.5b.json")
    cfg["name"] = "qwen2-0.5b-copy"
    (root / "bench" / "configs" / "qwen2-0.5b-copy.json").write_text(json.dumps(cfg))
    mix = load_json(REPO / "bench" / "traffic" / "qwen2-0.5b.long_decode.json")
    mix.update(config="qwen2-0.5b-copy", traffic="short")
    (root / "bench" / "traffic" / "qwen2-0.5b-copy.short.json").write_text(
        json.dumps(mix))
    (root / "bench" / "metrics" / "requests_done.py").write_text(
        "def read(ctx):\n    return 7\n")
    bench["configs"].append(dict(bench["configs"][1], name="qwen2-0.5b-copy",
                                 file="bench/configs/qwen2-0.5b-copy.json"))
    bench["workloads"].append({"name": "qwen2-0.5b-copy.short",
                               "config": "qwen2-0.5b-copy", "traffic": "short",
                               "chips": 1, "why": "copy"})
    bench["per_layer"].append({"name": "requests_done", "unit": "count",
                               "better": "higher", "source": "host_clock",
                               "layer": "scheduler", "moves": "ttft_p90_ms",
                               "workloads": ["qwen2-0.5b-copy.short"]})
    # A quantity split for the new cell shares the reader before its suffix.
    bench["per_layer"].append({"name": "admit_ms.short", "unit": "ms",
                               "better": "lower", "source": "device_trace",
                               "layer": "admit program", "moves": "ttft_p90_ms",
                               "workloads": ["qwen2-0.5b-copy.short"]})
    for m in bench["end_to_end"]:
        if "workloads" in m and "qwen2-0.5b.long_decode" in m["workloads"]:
            m["workloads"].append("qwen2-0.5b-copy.short")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    b, cell = harness.find_cell(root, "qwen2-0.5b-copy.short")
    assert cell.config["name"] == "qwen2-0.5b-copy"
    found = {e["name"]: r for e, r in cell.metrics(b, True)}
    assert list(found) == ["requests_done", "admit_ms.short"]
    assert found["admit_ms.short"].__file__.endswith("admit_ms.py")
    e2e = [e["name"] for e, _ in cell.metrics(b, False)]
    assert set(e2e) == {"ttft_p90_ms", "tpot_p90_ms", "setup_s"}
    _, old = harness.find_cell(root, "qwen2-0.5b.long_decode")
    assert "requests_done" not in [e["name"] for e, _ in old.metrics(b, True)]


def test_unknown_device_kind_is_an_error():
    assert harness.peaks_for(REPO, "TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError, match="no peaks"):
        harness.peaks_for(REPO, "TPU v99")


def test_no_chip_is_refused():
    with pytest.raises(harness.NoChip):
        harness.device_info(1)


def test_result_line_schema(tmp_path, monkeypatch):
    import jax

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "elsewhere"))
    r = run_tiny(tmp_path, "rwkv6-1.6b.chat")
    # The cache stays in the checkout, uncapped, whatever the environment.
    assert jax.config.jax_compilation_cache_dir == str(tmp_path / ".jax_cache")
    assert jax.config.jax_compilation_cache_max_size == -1
    assert (tmp_path / ".jax_cache").is_dir()
    assert list(r)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(r)[-1] == "checks"
    assert r["correct"] is True and r["attempted"] > 0 and r["failed"] == 0
    assert set(r["metrics"]) == {"ttft_p90_ms", "tpot_p90_ms", "setup_s"}
    for m in r["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(r["device"])
    assert r["compiles_in_window"] == 0
    for c in r["checks"].values():
        assert set(c) == {"value", "limit"} and c["value"] <= c["limit"]
    json.dumps(r)
