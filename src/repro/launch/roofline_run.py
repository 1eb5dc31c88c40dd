"""Exact roofline cost extraction via reduced-depth unrolled extrapolation.

XLA's HLO cost analysis visits while-loop bodies once, so the rolled
production program underreports FLOPs/bytes/collectives by the scan trip
counts.  Instead of unrolling the full model (compile-time explosion), we
exploit bilinearity: with L = layer periods and m = microbatches,

    cost(L, m) = a + b·L + c·m + d·L·m

(a: fixed embed/logits/optimizer-base work; b: per-period work incl. its
optimizer update; c: per-microbatch fixed work, e.g. logits per chunk;
d: per-period-per-microbatch work, e.g. FSDP param all-gathers).  Four
small *fully-unrolled* lowers — (L₁,1), (L₂,1), (L₁,2), (L₂,2) — identify
(a,b,c,d) exactly, and the full cell's cost is evaluated at
(n_periods, n_micro).  Remainder layers are included in both L points so
they fold into `a`.  Decode/prefill cells have no microbatch loop → 2
points suffice.  Token-proportional work is constant in m (each microbatch
carries 1/m of the batch), so it lands in a + b·L, as required.
"""

from __future__ import annotations

import dataclasses

from repro.configs.base import SHAPES
from repro.configs.registry import get_config
from repro.launch import roofline as R
from repro.core.lowering import unrolled_cost_mode
from repro.model.transformer import plan_groups


def _measure(arch, shape_name, cfg, *, multi_pod=False):
    """Lower one reduced config fully unrolled; return cost dict."""
    from repro.launch.dryrun import lower_cell

    with unrolled_cost_mode():
        lowered, mesh, rules, _, _ = lower_cell(
            arch, shape_name, multi_pod=multi_pod, cfg_override=cfg
        )
    compiled = lowered.compile()
    ca = compiled.cost_analysis()
    coll = R.parse_collective_bytes(compiled.as_text())
    out = {
        "flops": float(ca.get("flops", 0.0)),
        "bytes": float(ca.get("bytes accessed", 0.0)),
        "coll": float(coll["total_bytes"]),
        "coll_by_op": {
            k: v["bytes"] for k, v in coll.items() if isinstance(v, dict)
        },
    }
    del compiled, lowered
    return out


def _combine(c1, c2, w1, w2):
    out = {k: w1 * c1[k] + w2 * c2[k] for k in ("flops", "bytes", "coll")}
    out["coll_by_op"] = {
        k: w1 * c1["coll_by_op"][k] + w2 * c2["coll_by_op"][k]
        for k in c1["coll_by_op"]
    }
    return out


def extrapolated_costs(arch: str, shape_name: str, *, multi_pod: bool = False,
                       verbose: bool = True, base_cfg=None) -> dict:
    """Per-device HLO cost of the FULL cell, via bilinear extrapolation."""
    cfg = base_cfg if base_cfg is not None else get_config(arch)
    shape = SHAPES[shape_name]
    pattern, n_periods, remainder = plan_groups(cfg)
    p, r = len(pattern), len(remainder)
    l1, l2 = p + r, 2 * p + r

    enc_full = cfg.encoder_layers
    enc_ratio = enc_full / cfg.num_layers if enc_full else 0.0

    def reduced(n_layers, n_micro):
        return dataclasses.replace(
            cfg,
            num_layers=n_layers,
            microbatch=n_micro,
            encoder_layers=max(1, round(n_layers * enc_ratio)) if enc_full else 0,
        )

    has_micro = shape.kind == "train" and cfg.microbatch > 1
    n_micro_full = cfg.microbatch if shape.kind == "train" else 1

    c11 = _measure(arch, shape_name, reduced(l1, 1), multi_pod=multi_pod)
    c21 = _measure(arch, shape_name, reduced(l2, 1), multi_pod=multi_pod)
    # Per-period slope at m=1; intercept (embed/logits/opt + remainder).
    b1 = _combine(c21, c11, 1.0, -1.0)               # b + d   (at m=1)
    a1 = _combine(c11, b1, 1.0, -1.0)                # a + c   (at m=1)

    if has_micro:
        c12 = _measure(arch, shape_name, reduced(l1, 2), multi_pod=multi_pod)
        c22 = _measure(arch, shape_name, reduced(l2, 2), multi_pod=multi_pod)
        b2 = _combine(c22, c12, 1.0, -1.0)           # b + 2d
        d = _combine(b2, b1, 1.0, -1.0)              # d
        b = _combine(b1, d, 1.0, -1.0)               # b
        a2 = _combine(c12, b2, 1.0, -1.0)            # a + 2c
        c = _combine(a2, a1, 1.0, -1.0)              # c
        a = _combine(a1, c, 1.0, -1.0)               # a
        m = n_micro_full
        total = _combine(
            _combine(a, b, 1.0, float(n_periods)),
            _combine(c, d, float(m), float(n_periods * m)),
            1.0, 1.0,
        )
        points = {"c11": c11, "c21": c21, "c12": c12, "c22": c22}
    else:
        total = _combine(a1, b1, 1.0, float(n_periods))
        points = {"c11": c11, "c21": c21}

    if verbose:
        print(
            f"  roofline[{arch} {shape_name}]: flops/dev={total['flops']:.3e} "
            f"bytes/dev={total['bytes']:.3e} coll/dev={total['coll']:.3e}",
            flush=True,
        )
    return {
        "extrapolated": total,
        "n_periods": n_periods,
        "n_micro": n_micro_full,
        "points": points,
    }
