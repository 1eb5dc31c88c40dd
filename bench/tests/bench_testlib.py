"""Shared pieces of the benchmark's CPU tests: a checkout-shaped root
holding one cell cut to a tiny size, which ``harness.run`` drives
without a chip."""

from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
for p in (str(REPO / "src"), str(REPO)):
    if p not in sys.path:
        sys.path.insert(0, p)

#: Sizes of the tiny stand-ins (widths cut: CPU tests only).
TINY_MODEL = {
    "rwkv6-1.6b": dict(num_layers=2, d_model=128, num_heads=2,
                       num_kv_heads=2, head_dim=64, d_ff=256,
                       vocab_size=512),
    "qwen2-0.5b": dict(num_layers=2, d_model=128, num_heads=2,
                       num_kv_heads=1, head_dim=64, d_ff=256,
                       vocab_size=512),
}
TINY_MIX = {
    "serving": {"slots": 4, "decode_window": 4, "max_len": 256},
    "arrivals": {"process": "poisson", "rate_per_s": 6.0},
    "prompt_tokens": {"dist": "lognormal", "median": 40, "sigma": 0.5,
                      "min": 16, "max": 64},
    "output_tokens": {"dist": "lognormal", "median": 12, "sigma": 0.5,
                      "min": 4, "max": 24},
}


def load_json(path):
    with open(path) as f:
        return json.load(f)


def tiny_root(tmp: Path, workload: str, mix: dict | None = None) -> Path:
    """A root with the real BENCHMARK.json, metrics and references, and
    ``workload``'s configuration and mix cut to the tiny sizes."""
    (tmp / "bench").mkdir(parents=True, exist_ok=True)
    for d in ("configs", "metrics"):
        shutil.copytree(REPO / "bench" / d, tmp / "bench" / d,
                        dirs_exist_ok=True)
    shutil.copy(REPO / "bench" / "peaks.json", tmp / "bench" / "peaks.json")
    shutil.copy(REPO / "BENCHMARK.json", tmp / "BENCHMARK.json")
    bench = load_json(REPO / "BENCHMARK.json")
    entry = next(w for w in bench["workloads"] if w["name"] == workload)
    real = load_json(REPO / "bench" / "traffic" / f"{workload}.json")
    real.update(TINY_MIX if mix is None else mix)
    real["check"] = dict(real["check"], requests=4)
    (tmp / "bench" / "traffic").mkdir(exist_ok=True)
    with open(tmp / "bench" / "traffic" / f"{workload}.json", "w") as f:
        json.dump(real, f)
    cfg = load_json(REPO / "bench" / "configs" / f"{entry['config']}.json")
    cfg["model"].update(TINY_MODEL[entry["config"]])
    with open(tmp / "bench" / "configs" / f"{entry['config']}.json", "w") as f:
        json.dump(cfg, f)
    return tmp


def run_tiny(tmp: Path, workload: str, seed: int = 2**33 + 5,
             seconds: float = 2.0, **kw) -> dict:
    from bench import harness

    root = tiny_root(tmp, workload)
    return harness.run(workload, seed, seconds, False, root=root,
                       require_chip=False, log=lambda m: None, **kw)
