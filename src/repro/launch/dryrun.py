"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

THE VERY FIRST TWO LINES (before any other import, including repro.*) force
512 placeholder host devices so jax.make_mesh can build the production
meshes — jax locks the device count on first init. This flag is set ONLY
here: smoke tests and benches see the single real CPU device.
"""
import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=512 "
                           + os.environ.get("XLA_FLAGS", ""))

import argparse                                                  # noqa: E402
import json                                                      # noqa: E402
import time                                                      # noqa: E402
import traceback                                                 # noqa: E402
from typing import Any, Dict, Optional                           # noqa: E402

import jax                                                       # noqa: E402
import jax.numpy as jnp                                          # noqa: E402
import numpy as np                                               # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P      # noqa: E402

from repro.configs.base import ShapeCell                         # noqa: E402
from repro.configs.registry import (ARCHS, get_config,           # noqa: E402
                                    input_specs, iter_cells)
from repro.distributed.sharding import (default_rules,           # noqa: E402
                                        param_shardings, spec_for,
                                        use_mesh_rules)
from repro.launch.mesh import make_auto_mesh, make_production_mesh  # noqa: E402
from repro.models import model as M                              # noqa: E402
from repro.models.nn import axes_tree                            # noqa: E402
from repro.roofline.analysis import (from_compiled,              # noqa: E402
                                     model_flops_for_cell)
from repro.serving import engine as E                            # noqa: E402
from repro.training import optimizer as O                        # noqa: E402
from repro.training.train_step import (TrainConfig, TrainState,  # noqa: E402
                                       train_step)


# ---------------------------------------------------------------------------
# shardings
# ---------------------------------------------------------------------------

def _params_shapes_and_axes(cfg, key_spec):
    axes_store: Dict[str, Any] = {}

    def init_fn(key):
        params, axes = M.init_params(cfg, key)
        axes_store.update(axes)
        return params

    shapes = jax.eval_shape(init_fn, key_spec)
    return shapes, axes_tree(shapes, axes_store)


def _state_shardings(cfg, mesh, rules, p_shapes, p_axes):
    psh = param_shardings(p_axes, p_shapes, rules, mesh)
    rep = NamedSharding(mesh, P())
    return TrainState(
        params=psh,
        opt=O.OptState(step=rep, mu=psh, nu=psh, master=psh))


def _batch_axes(multi_pod):
    return ("pod", "data") if multi_pod else ("data",)


def _batch_part(mesh, multi_pod, batch: int):
    """Batch-dim partition with divisibility fallback (long_500k has B=1)."""
    axes = [a for a in _batch_axes(multi_pod) if a in mesh.shape]
    total = int(np.prod([mesh.shape[a] for a in axes])) if axes else 1
    if total <= 1 or batch % total != 0:
        return None
    return tuple(axes) if len(axes) > 1 else axes[0]


def _cache_shardings(cfg, cache_shapes, mesh, rules):
    """NamedShardings for a decode cache pytree by leaf role."""
    batch = rules.acts["batch"]

    def leaf_spec(path, leaf):
        name = "/".join(str(getattr(p, "key", p)) for p in path)
        if leaf.ndim == 0 or "pos" in name:
            return P()
        if name.endswith("k") or name.endswith("v"):
            # (L, B, ring, Kh, Dh) — Dh absorbs 'model' when Kh can't
            ax = (None, "batch", None, "kv_heads", "head")
        elif "ssm" in name:
            ax = (None, "batch", "heads_model", None, None)
        elif "conv" in name:
            ax = (None, "batch", None, "mlp")
        else:
            ax = (None,) * leaf.ndim
        rule = dict(rules.acts)
        rule["kv_heads"] = "model"
        rule["heads_model"] = "model"
        rule["head"] = None          # spec_for fallback may claim 'model'
        rule["mlp"] = "model"
        rule["batch"] = batch
        return spec_for(ax, leaf.shape, rule, mesh, head_fallback=True)

    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: NamedSharding(mesh, leaf_spec(path, leaf)),
        cache_shapes)


# ---------------------------------------------------------------------------
# per-cell lowering
# ---------------------------------------------------------------------------

def lower_cell(arch: str, cell: ShapeCell, *, multi_pod: bool,
               rules=None, extra_tag: str = "",
               cfg_override=None, tc: Optional[TrainConfig] = None,
               mesh_override=None) -> Dict[str, Any]:
    """Lower + compile one cell; return dry-run record (or raise).

    mesh_override: (shape_tuple, axes_tuple) — §Perf hillclimb alternative
    meshes (e.g. ((64, 4), ("data", "model"))), chips must still total
    256/512 so comparisons stay per-fleet.
    """
    cfg = cfg_override or get_config(arch)
    if mesh_override is not None:
        shape, axes = mesh_override
        mesh = make_auto_mesh(shape, axes)
        mesh_name = "x".join(map(str, shape)) + extra_tag
    else:
        mesh = make_production_mesh(multi_pod=multi_pod)
        mesh_name = ("2x16x16" if multi_pod else "16x16") + extra_tag
    chips = int(np.prod(list(mesh.shape.values())))
    if rules is None:
        rules = default_rules(multi_pod=multi_pod)
    batch_ax = _batch_part(mesh, multi_pod, cell.global_batch)

    key_spec = jax.ShapeDtypeStruct((2,), jnp.uint32)
    p_shapes, p_axes = _params_shapes_and_axes(cfg, key_spec)
    specs = input_specs(cfg, cell)
    t0 = time.monotonic()

    with mesh:
        with use_mesh_rules(mesh, rules):
            if cell.kind == "train":
                # microbatches=4: grad-accum bounds live activations so the
                # 4k x 256 train cells fit 16 GB HBM (see EXPERIMENTS.md)
                tc = tc or TrainConfig(microbatches=4)
                st_shapes = TrainState(
                    params=p_shapes,
                    opt=jax.eval_shape(O.init, p_shapes))
                st_sh = _state_shardings(cfg, mesh, rules, p_shapes, p_axes)
                tok_sh = NamedSharding(mesh, P(batch_ax, None))
                in_sh = [st_sh, tok_sh]
                args = [st_shapes, specs["tokens"]]
                if "memory" in specs:
                    in_sh.append(NamedSharding(mesh, P(batch_ax, None, None)))
                    args.append(specs["memory"])

                def step(state, tokens, memory=None):
                    return train_step(cfg, tc, state, tokens, memory)

                jitted = jax.jit(step, in_shardings=tuple(in_sh),
                                 donate_argnums=(0,))
                lowered = jitted.lower(*args)

            elif cell.kind == "prefill":
                psh = param_shardings(p_axes, p_shapes, rules, mesh)
                tok_sh = NamedSharding(mesh, P(batch_ax, None))
                in_sh = [psh, tok_sh]
                args = [p_shapes, specs["tokens"]]
                if "memory" in specs:
                    in_sh.append(NamedSharding(mesh, P(batch_ax, None, None)))
                    args.append(specs["memory"])

                def step(params, tokens, memory=None):
                    return E.prefill(params, cfg, tokens, cell.seq_len,
                                     memory=memory)

                jitted = jax.jit(step, in_shardings=tuple(in_sh))
                lowered = jitted.lower(*args)

            else:  # decode
                psh = param_shardings(p_axes, p_shapes, rules, mesh,
                                      head_fallback=True)
                cache_sh = _cache_shardings(cfg, specs["cache"], mesh, rules)
                tok_sh = NamedSharding(mesh, P(batch_ax, None))

                def step(params, cache, token):
                    return E.decode_step(params, cfg, cache, token)

                jitted = jax.jit(
                    step, in_shardings=(psh, cache_sh, tok_sh),
                    donate_argnums=(1,))
                lowered = jitted.lower(p_shapes, specs["cache"],
                                       specs["token"])

            t_lower = time.monotonic() - t0
            compiled = lowered.compile()
            t_compile = time.monotonic() - t0 - t_lower

    mf = model_flops_for_cell(cfg, cell, p_shapes)
    rl = from_compiled(compiled, arch=arch, cell=cell.name,
                       mesh_name=mesh_name, chips=chips, model_flops=mf)
    mem = compiled.memory_analysis()
    rec = rl.row()
    rec.update({
        "ok": True,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "arg_gb": getattr(mem, "argument_size_in_bytes", 0) / 1e9,
        "temp_gb": getattr(mem, "temp_size_in_bytes", 0) / 1e9,
        "out_gb": getattr(mem, "output_size_in_bytes", 0) / 1e9,
    })
    return rec


def run_sweep(archs, cells, multi_pod: bool, out_path: Optional[str],
              resume: bool = True) -> Dict:
    """Sweep cells; append-write JSONL so an interrupted sweep resumes."""
    done = set()
    if out_path and resume and os.path.exists(out_path):
        with open(out_path) as f:
            for line in f:
                r = json.loads(line)
                if r.get("ok"):          # failed cells retry on resume
                    done.add((r["arch"], r["cell"], r["mesh"]))
    results = []
    mesh_name = "2x16x16" if multi_pod else "16x16"
    for arch in archs:
        for cell, skip in iter_cells(arch):
            if cells and cell.name not in cells:
                continue
            key = (arch, cell.name, mesh_name)
            if key in done:
                continue
            if skip:
                rec = {"arch": arch, "cell": cell.name, "mesh": mesh_name,
                       "ok": True, "skipped": skip}
            else:
                print(f"--- {arch} x {cell.name} x {mesh_name}", flush=True)
                try:
                    rec = lower_cell(arch, cell, multi_pod=multi_pod)
                    print(f"    ok: compile {rec['compile_s']}s "
                          f"bottleneck={rec['bottleneck']} "
                          f"perdev={rec['per_device_gb']:.2f}GB", flush=True)
                except Exception as e:                     # noqa: BLE001
                    traceback.print_exc()
                    rec = {"arch": arch, "cell": cell.name,
                           "mesh": mesh_name, "ok": False, "error": str(e)}
            results.append(rec)
            if out_path:
                with open(out_path, "a") as f:
                    f.write(json.dumps(rec) + "\n")
    return {"results": results}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, help="one arch id (default all)")
    ap.add_argument("--cell", default=None,
                    help="one of train_4k/prefill_32k/decode_32k/long_500k")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--out", default=None, help="JSONL output path")
    ap.add_argument("--no-resume", action="store_true")
    args = ap.parse_args()
    archs = [args.arch] if args.arch else list(ARCHS)
    cells = [args.cell] if args.cell else None
    out = run_sweep(archs, cells, args.multi_pod, args.out,
                    resume=not args.no_resume)
    n_ok = sum(1 for r in out["results"] if r.get("ok"))
    print(f"\n{n_ok}/{len(out['results'])} cells OK")
    if any(not r.get("ok") for r in out["results"]):
        raise SystemExit(1)


if __name__ == "__main__":
    main()
