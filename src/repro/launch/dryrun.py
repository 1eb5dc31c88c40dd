import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

The two lines above MUST run before any other import (jax locks the device
count at first init); 512 host-platform placeholder devices back the
(2, 16, 16) production mesh.  Nothing is executed — ``.lower().compile()``
proves the distribution config is coherent, ``memory_analysis()`` proves it
fits, ``cost_analysis()`` + HLO collective parsing feed §Roofline.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch gemma3-1b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--out DIR]
"""

import argparse
import dataclasses
import json
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import SHAPES
from repro.configs.registry import get_config, list_archs
from repro.launch import inputs as I
from repro.launch import roofline as R
from repro.launch.mesh import make_production_mesh
from repro.model import model as M
from repro.model.sharding import make_rules, sharding_context, to_pspec
from repro.optim import adamw
from repro.serve import engine
from repro.train import step as train_mod

DEFAULT_OUT = Path(__file__).resolve().parents[3] / "experiments" / "dryrun"


def _named(mesh, spec_tree):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        spec_tree,
        is_leaf=lambda x: isinstance(x, P),
    )


def _mode_for(shape_name: str, kind: str) -> str:
    if kind == "train":
        return "train"
    if kind == "prefill":
        return "prefill"
    return "decode_long" if shape_name == "long_500k" else "decode"


def lower_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
               cfg_override=None):
    """Build (lowered, mesh, rules) for one cell. Raises on inapplicable."""
    cfg = cfg_override if cfg_override is not None else get_config(arch)
    shape = SHAPES[shape_name]
    ok, why = I.cell_is_applicable(cfg, shape_name)
    if not ok:
        raise SkipCell(why)

    mesh = make_production_mesh(multi_pod=multi_pod)
    mode = _mode_for(shape_name, shape.kind)
    rules = make_rules(mesh, mode)

    if shape.kind == "train":
        state_specs = train_mod.train_state_pspecs(cfg, rules)
        state_sds = train_mod.abstract_train_state(cfg)
        batch_sds, batch_axes = I.batch_specs(cfg, shape)
        batch_specs_tree = I.resolve_pspecs(batch_axes, rules)
        step_fn = train_mod.make_train_step(cfg)

        def fn(state, batch):
            new_state, metrics = step_fn(state, batch)
            return new_state, metrics

        in_sh = (_named(mesh, state_specs), _named(mesh, batch_specs_tree))
        with mesh, sharding_context(mesh, rules):
            lowered = jax.jit(
                fn, in_shardings=in_sh, donate_argnums=(0,)
            ).lower(state_sds, batch_sds)

    elif shape.kind == "prefill":
        params_specs = M.param_pspecs(cfg, rules)
        params_sds = M.abstract_params(cfg)
        batch_sds, batch_axes = I.batch_specs(cfg, shape)
        batch_specs_tree = I.resolve_pspecs(batch_axes, rules)
        prefill = engine.make_prefill_step(cfg)

        def fn(params, batch):
            kw = {}
            if "frontend_embeds" in batch:
                kw["frontend_embeds"] = batch["frontend_embeds"]
            if "positions" in batch:
                kw["positions"] = batch["positions"]
            if "enc_embeds" in batch:
                kw["enc_tokens_embeds"] = batch["enc_embeds"]
            return prefill(params, batch["tokens"], **kw)

        in_sh = (_named(mesh, params_specs), _named(mesh, batch_specs_tree))
        with mesh, sharding_context(mesh, rules):
            lowered = jax.jit(fn, in_shardings=in_sh).lower(params_sds, batch_sds)

    else:  # decode
        import dataclasses as dc

        dcfg = dc.replace(cfg, remat="none", microbatch=1)
        params_specs = M.param_pspecs(dcfg, rules)
        params_sds = M.abstract_params(dcfg)
        state_sds, tok_sds, len_sds, extras, extras_axes = I.decode_specs(dcfg, shape)
        state_specs = M.decode_state_pspecs(
            dcfg, shape.global_batch, shape.seq_len, rules
        )
        decode = engine.make_decode_step(dcfg)

        if extras:
            enc_spec = to_pspec(extras_axes["enc_out"], rules)

            def fn(params, state, tokens, length, enc_out):
                return decode(params, state, tokens, length, enc_out=enc_out)

            in_sh = (
                _named(mesh, params_specs), _named(mesh, state_specs),
                NamedSharding(mesh, to_pspec(("batch", None), rules)),
                NamedSharding(mesh, P()),
                NamedSharding(mesh, enc_spec),
            )
            args = (params_sds, state_sds, tok_sds, len_sds, extras["enc_out"])
        else:
            def fn(params, state, tokens, length):
                return decode(params, state, tokens, length)

            in_sh = (
                _named(mesh, params_specs), _named(mesh, state_specs),
                NamedSharding(mesh, to_pspec(("batch", None), rules)),
                NamedSharding(mesh, P()),
            )
            args = (params_sds, state_sds, tok_sds, len_sds)

        with mesh, sharding_context(mesh, rules):
            lowered = jax.jit(fn, in_shardings=in_sh, donate_argnums=(1,)).lower(*args)

    return lowered, mesh, rules, cfg, shape


class SkipCell(Exception):
    pass


def run_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
             out_dir: Path | None = None, verbose: bool = True,
             roofline: bool = True) -> dict:
    mesh_tag = "2x16x16" if multi_pod else "16x16"
    tag = f"{arch}__{shape_name}__{mesh_tag}"
    result = {"arch": arch, "shape": shape_name, "mesh": mesh_tag}
    t0 = time.time()
    try:
        lowered, mesh, rules, cfg, shape = lower_cell(
            arch, shape_name, multi_pod=multi_pod
        )
        result["lower_s"] = round(time.time() - t0, 1)
        t1 = time.time()
        compiled = lowered.compile()
        result["compile_s"] = round(time.time() - t1, 1)

        ma = compiled.memory_analysis()
        result["memory"] = {
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "alias_bytes": ma.alias_size_in_bytes,
            "peak_estimate_bytes": ma.argument_size_in_bytes
            + ma.output_size_in_bytes
            + ma.temp_size_in_bytes
            - ma.alias_size_in_bytes,
        }
        ca = compiled.cost_analysis()
        result["cost"] = {
            "flops": float(ca.get("flops", 0.0)),
            "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
        }
        hlo = compiled.as_text()
        result["hlo_bytes"] = len(hlo)
        coll = R.parse_collective_bytes(hlo)
        result["collectives_raw"] = coll
        del compiled, lowered, hlo

        # Raw rolled-program numbers undercount while-loop bodies; the
        # roofline terms come from the exact bilinear extrapolation over
        # reduced-depth unrolled lowers (single-pod only, per spec).
        if roofline and not multi_pod:
            from repro.launch.roofline_run import extrapolated_costs

            ex = extrapolated_costs(arch, shape_name, multi_pod=False)
            tot = ex["extrapolated"]
            terms = R.roofline_terms(
                {"flops": tot["flops"], "bytes accessed": tot["bytes"]},
                {"total_bytes": tot["coll"]},
            )
            result["roofline"] = terms.as_dict()
            # Fused-execution HBM estimate (CPU HLO bytes are unfused; see
            # roofline.analytic_hbm_bytes docstring + EXPERIMENTS.md).
            mode = _mode_for(shape_name, shape.kind)
            ana = R.analytic_hbm_bytes(cfg, shape, 256, mode)
            result["roofline"]["memory_analytic_s"] = ana / 819e9
            result["roofline"]["hbm_bytes_analytic"] = ana
            terms_f = {
                "compute": result["roofline"]["compute_s"],
                "memory(fused est)": result["roofline"]["memory_analytic_s"],
                "collective": result["roofline"]["collective_s"],
            }
            result["roofline"]["dominant_fused"] = max(terms_f, key=terms_f.get)
            result["collectives_by_op"] = tot["coll_by_op"]
            result["model_flops"] = R.model_flops(cfg, shape)
            n_chips = 512 if multi_pod else 256
            result["model_flops_per_chip"] = result["model_flops"] / n_chips
            result["useful_flops_ratio"] = (
                result["model_flops_per_chip"] / tot["flops"]
                if tot["flops"]
                else None
            )
        result["ok"] = True
    except SkipCell as e:
        result["ok"] = True
        result["skipped"] = str(e)
    except Exception as e:  # noqa: BLE001 — reported as a failed cell
        result["ok"] = False
        result["error"] = f"{type(e).__name__}: {e}"
        result["traceback"] = traceback.format_exc()[-4000:]

    result["total_s"] = round(time.time() - t0, 1)
    if out_dir is not None:
        out_dir.mkdir(parents=True, exist_ok=True)
        (out_dir / f"{tag}.json").write_text(json.dumps(result, indent=1))
    if verbose:
        status = "SKIP" if result.get("skipped") else ("OK" if result["ok"] else "FAIL")
        extra = ""
        if "roofline" in result:
            r = result["roofline"]
            extra = (f" dom={r.get('dominant_fused', r['dominant'])} "
                     f"comp={r['compute_s']:.4f}s mem={r['memory_s']:.4f}s "
                     f"memF={r.get('memory_analytic_s', 0):.4f}s "
                     f"coll={r['collective_s']:.4f}s")
        if "memory" in result:
            extra += f" peak={result['memory']['peak_estimate_bytes']/2**30:.2f}GiB"
        print(f"[{status}] {tag} ({result['total_s']}s){extra}", flush=True)
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES) + [None])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default=str(DEFAULT_OUT))
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--no-roofline", action="store_true")
    args = ap.parse_args()

    out_dir = Path(args.out)
    archs = [args.arch] if args.arch else list(list_archs())
    shapes = [args.shape] if args.shape else list(SHAPES)
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    failures = 0
    for multi_pod in meshes:
        for arch in archs:
            for shape in shapes:
                mesh_tag = "2x16x16" if multi_pod else "16x16"
                tag = f"{arch}__{shape}__{mesh_tag}"
                if args.skip_existing and (out_dir / f"{tag}.json").exists():
                    prev = json.loads((out_dir / f"{tag}.json").read_text())
                    if prev.get("ok"):
                        print(f"[CACHED] {tag}", flush=True)
                        continue
                res = run_cell(arch, shape, multi_pod=multi_pod, out_dir=out_dir,
                               roofline=not args.no_roofline)
                failures += 0 if res["ok"] else 1
    print(f"done; failures={failures}", flush=True)
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()
