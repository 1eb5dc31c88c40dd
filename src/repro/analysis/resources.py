"""Pallas resource checker: static VMEM footprints vs the per-core budget.

Every Pallas kernel in the repo declares its geometry next to its
``pallas_call`` (``kernels.common.register_kernel_resources``): grid,
BlockSpec block shapes, scratch shapes.  This pass evaluates those
declarations for a config — *full-size*, not the smoke-reduced variant,
because the whole point is catching a production shape that only blows
VMEM on hardware — and checks, with pure shape arithmetic:

* the estimated VMEM high-water mark (double-buffered in/out blocks +
  scratch) fits the per-core budget;
* the grid is well-formed (every dim >= 1);
* the geometry validators the wrappers share (``validate_divisible``,
  ``pick_d_block``, chunk resolution) accept the config — a spec fn
  raising is converted into an error finding, so an indivisible
  ``d_rnn`` or a chunk smaller than ``conv_width`` is caught before any
  array exists;
* for the WKV decode window, the declared state tile agrees with the
  cost model's per-window state bytes (``wkv_decode_traffic`` direct) —
  the kernel and the model it is benchmarked against cannot drift apart.

Nothing is traced, lowered, or executed: this pass is plain integer math
over declared shapes, so it runs in microseconds for any config.
"""

from __future__ import annotations

import math

from repro.analysis.findings import Finding, error, info

PASS = "resources"

#: Per-core VMEM budget (bytes).  TPU v4/v5 cores expose ~16 MiB of VMEM;
#: a kernel whose working set exceeds this fails to compile on hardware —
#: on this CPU container it would only fail in interpret-mode silence.
VMEM_BUDGET_BYTES = 16 * 2 ** 20


def _load_specs():
    """Import every kernel ops module (registration side effects), then
    return the registry."""
    import repro.kernels.elevator_scan.ops  # noqa: F401
    import repro.kernels.local_attention.ops  # noqa: F401
    import repro.kernels.token_shift.ops  # noqa: F401
    import repro.kernels.wkv.ops  # noqa: F401
    from repro.kernels.common import KERNEL_RESOURCE_SPECS

    return KERNEL_RESOURCE_SPECS


def check_resources(res, *, budget: int = VMEM_BUDGET_BYTES,
                    what: str = "") -> list[Finding]:
    """Budget + well-formedness checks for one declaration."""
    findings: list[Finding] = []
    label = f"{what}{res.kernel}"
    if not res.grid or any(g < 1 for g in res.grid):
        findings.append(error(
            PASS, res.location,
            f"{label}: malformed grid {res.grid}",
        ))
        return findings
    vm = res.vmem_bytes()
    if vm > budget:
        findings.append(error(
            PASS, res.location,
            f"{label}: estimated VMEM {vm / 2**10:.0f} KiB exceeds the "
            f"{budget / 2**20:.0f} MiB per-core budget "
            f"(blocks {res.block_bytes()} B x2 + scratch "
            f"{res.scratch_bytes()} B)",
            vmem_bytes=vm, budget_bytes=budget,
        ))
    else:
        findings.append(info(
            PASS, res.location,
            f"{label}: grid {res.grid} ({res.grid_steps()} steps), "
            f"estimated VMEM {vm / 2**10:.0f} KiB of "
            f"{budget / 2**20:.0f} MiB",
            vmem_bytes=vm, grid_steps=res.grid_steps(),
        ))
    return findings


def crosscheck_decode_state(cfg, res) -> list[Finding]:
    """Declared WKV decode state tile vs the cost model's per-window
    state bytes (``wkv_decode_traffic`` direct: one read + one write)."""
    from repro.core import cost_model

    dh = None
    declared = 0
    for name, shape, isz in res.blocks:
        if name in ("h0", "s_out"):
            declared += math.prod(shape) * isz
            dh = shape[-1]
    if dh is None:
        return [error(
            PASS, res.location,
            f"{cfg.name} {res.kernel}: no state blocks (h0/s_out) declared "
            f"— cannot cross-check against wkv_decode_traffic",
        )]
    b = 1
    h = res.grid[1]
    # The whole K-token window is one block: K is the r block's seq dim.
    k = dict((n, s) for n, s, _ in res.blocks)["r"][2]
    costs = {c.variant: c for c in cost_model.wkv_decode_traffic(b, h, dh, k)}
    tok_io = cost_model.wkv_decode_token_io(b, h, dh, k)
    modeled = costs["direct"].traffic.dram_bytes - tok_io
    # Declared per-(batch,head) tile x the (b, h) grid extent = the HBM
    # bytes the window actually moves for S.
    counted = declared * res.grid[0] * h
    if counted != modeled:
        return [error(
            PASS, res.location,
            f"{cfg.name} {res.kernel}: declared state traffic {counted} B "
            f"!= cost model's {modeled} B per window — kernel and "
            f"wkv_decode_traffic drifted apart",
            counted_bytes=counted, modeled_bytes=modeled,
        )]
    return [info(
        PASS, res.location,
        f"{cfg.name} {res.kernel}: state HBM traffic matches "
        f"wkv_decode_traffic direct ({counted} B/window)",
        state_bytes=counted,
    )]


def run(cfg, *, budget: int = VMEM_BUDGET_BYTES) -> list[Finding]:
    """Audit every registered kernel declaration applicable to ``cfg``
    (the FULL config — production shapes, not the smoke reduction)."""
    specs = _load_specs()
    findings: list[Finding] = []
    applicable = 0
    for name in sorted(specs):
        try:
            res = specs[name](cfg)
        except Exception as e:  # noqa: BLE001 — invalid geometry IS a finding
            findings.append(error(
                PASS, f"src/repro/kernels:{name}",
                f"{cfg.name} {name}: invalid kernel geometry: {e}",
            ))
            continue
        if res is None:
            continue
        applicable += 1
        findings += check_resources(res, budget=budget, what=f"{cfg.name} ")
        if name == "wkv.decode_window":
            findings += crosscheck_decode_state(cfg, res)
    if applicable == 0:
        findings.append(error(
            PASS, "src/repro/kernels/common.py:KERNEL_RESOURCE_SPECS",
            f"{cfg.name}: no registered kernel resource spec applies — "
            f"registry wiring is broken for pattern {tuple(cfg.pattern)}",
        ))
    return findings
