import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

This is how the distribution config is proven coherent without hardware:
``jax.jit(step).lower(...).compile()`` on 512 placeholder CPU devices runs
the full SPMD partitioner; sharding mismatches, compile-time OOMs and
unsupported collectives all surface here.  The compiled artifact yields the
roofline terms (EXPERIMENTS.md §Roofline):

    compute_s    = HLO flops per device / 197e12      (v5e bf16 peak)
    memory_s     = HLO bytes per device / 819e9       (HBM bandwidth)
    collective_s = collective bytes (from the partitioned HLO) / 50e9 (ICI)

Usage:
    python -m repro.launch.dryrun --arch all --shape all --mesh both \
        --out experiments/dryrun
"""

import argparse
import json
import re
import sys
import time
import traceback
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..compile_cache import use_compile_cache
from ..configs import ARCH_IDS, SHAPES, ShapeSpec, get_config, shape_applicable
from ..models import encdec as ED
from ..models import transformer as T
from ..models.config import ModelConfig
from ..nn.params import ParamSpec, param_count
from ..optim import AdamWState
from ..optim.schedule import warmup_cosine
from ..runtime.train_loop import TrainState, make_train_step, model_spec_for
from ..sharding import activation_sharding, logical_to_pspec, shardings_for_axes
from ..sharding.context import ACT_RULES
from .mesh import HW, make_production_mesh

_IS_SPEC = lambda x: isinstance(x, ParamSpec)


# ---------------------------------------------------------------------------
# ShapeDtypeStruct builders (no allocation anywhere)
# ---------------------------------------------------------------------------


def _sds(shape, dtype, mesh, axes) -> jax.ShapeDtypeStruct:
    ps = logical_to_pspec(axes, mesh, shape, rules=ACT_RULES)
    return jax.ShapeDtypeStruct(shape, dtype, sharding=NamedSharding(mesh, ps))


def param_sds(cfg: ModelConfig, mesh, dtype=None):
    spec = model_spec_for(cfg)

    def one(l: ParamSpec):
        return _sds(l.shape, dtype or l.dtype, mesh, l.axes)

    return jax.tree_util.tree_map(one, spec, is_leaf=_IS_SPEC)


def state_sds(cfg: ModelConfig, mesh, *, moment_dtype=None) -> TrainState:
    p = param_sds(cfg, mesh)
    m = param_sds(cfg, mesh, dtype=moment_dtype) if moment_dtype else p
    scalar = jax.ShapeDtypeStruct((), jnp.int32, sharding=NamedSharding(mesh, P()))
    return TrainState(
        params=p,
        opt=AdamWState(mu=m, nu=m, count=scalar),
        step=scalar,
    )


def cache_sds(cfg: ModelConfig, mesh, batch: int, seq_budget: int):
    if cfg.is_encdec:
        shapes = jax.eval_shape(
            lambda: ED.init_encdec_cache(cfg, batch, seq_budget, seq_budget, cfg.dtype)
        )
        ax_attn = {"k": ("layers", "batch", "seq", "kv_heads", "head_dim"),
                   "v": ("layers", "batch", "seq", "kv_heads", "head_dim"),
                   "pos": ("layers", "seq")}
        axes = {
            "units": tuple(ax_attn for _ in cfg.pattern),
            "cross_kv": tuple(
                (("layers", "batch", "seq", "kv_heads", "head_dim"),) * 2
                for _ in cfg.pattern
            ),
        }
    else:
        shapes = jax.eval_shape(lambda: T.init_cache(cfg, batch, seq_budget, cfg.dtype))
        axes = T.cache_axes(cfg)

    def one(s, a):
        return _sds(s.shape, s.dtype, mesh, a)

    return jax.tree_util.tree_map(
        one, shapes, axes,
        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct),
    )


def input_specs(cfg: ModelConfig, shape: ShapeSpec, mesh) -> Dict[str, Any]:
    """ShapeDtypeStruct stand-ins for every model input of this cell."""
    B, S = shape.global_batch, shape.seq_len
    tok = lambda b, s: _sds((b, s), jnp.int32, mesh, ("batch", "seq"))
    out: Dict[str, Any] = {}
    if shape.kind == "train":
        A = max(cfg.train_accum, 1)
        mb = B // A
        # accumulation unit dim leads when A > 1 (the DFPA unit axis)
        lead = (A,) if A > 1 else ()
        lax_ = (None,) if A > 1 else ()
        atok = lambda s: _sds(lead + (mb, s), jnp.int32, mesh, lax_ + ("batch", "seq"))
        if cfg.is_encdec:
            out["batch"] = {
                "frames": _sds(lead + (mb, S, cfg.d_model), jnp.float32, mesh,
                               lax_ + ("batch", "seq", "embed_act")),
                "tokens": atok(S),
                "labels": atok(S),
            }
        else:
            s_text = S - cfg.num_prefix_embeddings
            out["batch"] = {"tokens": atok(s_text), "labels": atok(s_text)}
            if cfg.frontend == "vision_stub":
                out["batch"]["prefix_embeds"] = _sds(
                    lead + (mb, cfg.num_prefix_embeddings, cfg.d_model), jnp.float32,
                    mesh, lax_ + ("batch", "seq", "embed_act"),
                )
    elif shape.kind == "prefill":
        out["caches"] = cache_sds(cfg, mesh, B, S)
        if cfg.is_encdec:
            out["frames"] = _sds((B, S, cfg.d_model), jnp.float32, mesh, ("batch", "seq", "embed_act"))
            out["tokens"] = tok(B, S)
        else:
            s_text = S - cfg.num_prefix_embeddings
            out["tokens"] = tok(B, s_text)
            if cfg.frontend == "vision_stub":
                out["prefix_embeds"] = _sds(
                    (B, cfg.num_prefix_embeddings, cfg.d_model), jnp.float32, mesh,
                    ("batch", "seq", "embed_act"),
                )
    else:  # decode
        out["caches"] = cache_sds(cfg, mesh, B, S)
        out["token"] = tok(B, 1)
        out["pos"] = jax.ShapeDtypeStruct((), jnp.int32, sharding=NamedSharding(mesh, P()))
    return out


# ---------------------------------------------------------------------------
# Step functions to lower
# ---------------------------------------------------------------------------


def reduced_units(cfg: ModelConfig, units: int) -> ModelConfig:
    """Same family/widths, ``units`` pattern repetitions (prefix kept)."""
    kw = dict(num_layers=len(cfg.prefix) + units * len(cfg.pattern))
    if cfg.is_encdec:
        kw["encoder_layers"] = units * len(cfg.encoder_pattern)
    return cfg.replace(**kw)


def build_step(cfg: ModelConfig, shape: ShapeSpec, mesh):
    """Returns (fn, example_args: tuple, donate) ready for jit."""
    if shape.kind == "train":
        step = make_train_step(
            cfg, warmup_cosine(3e-4, 100, 10_000),
            accum_steps=max(cfg.train_accum, 1),
        )
        ins = input_specs(cfg, shape, mesh)
        mdt = jnp.bfloat16 if os.environ.get("REPRO_BF16_MOMENTS") else None
        return step, (state_sds(cfg, mesh, moment_dtype=mdt), ins["batch"]), (0,)

    sparams = param_sds(cfg, mesh, dtype=cfg.dtype)  # bf16 serving weights
    ins = input_specs(cfg, shape, mesh)
    if shape.kind == "prefill":
        if cfg.is_encdec:
            def fn(params, frames, tokens, caches):
                return ED.encdec_prefill(params, cfg, frames, tokens, caches)

            return fn, (sparams, ins["frames"], ins["tokens"], ins["caches"]), (3,)
        if cfg.frontend == "vision_stub":
            def fn(params, tokens, prefix_embeds, caches):
                return T.prefill(params, cfg, tokens, caches, prefix_embeds=prefix_embeds)

            return fn, (sparams, ins["tokens"], ins["prefix_embeds"], ins["caches"]), (3,)

        def fn(params, tokens, caches):
            return T.prefill(params, cfg, tokens, caches)

        return fn, (sparams, ins["tokens"], ins["caches"]), (2,)

    # decode
    if cfg.is_encdec:
        def fn(params, token, pos, caches):
            return ED.encdec_decode_step(params, cfg, token, pos, caches)
    else:
        def fn(params, token, pos, caches):
            return T.decode_step(params, cfg, token, pos, caches)

    return fn, (sparams, ins["token"], ins["pos"], ins["caches"]), (3,)


# ---------------------------------------------------------------------------
# HLO collective analysis
# ---------------------------------------------------------------------------

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16, "token": 0,
}

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_COLL_RE = re.compile(
    r"=\s*(\([^=]*?\)|[a-z0-9]+\[[0-9,]*\][^\s]*)\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(-start)?\("
)


def _shape_bytes(text: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(text):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_stats(hlo_text: str) -> Dict[str, Dict[str, float]]:
    """Sum result-shape bytes per collective type from partitioned HLO.
    ``-done`` ops are skipped (their ``-start`` was counted)."""
    stats: Dict[str, Dict[str, float]] = {}
    for m in _COLL_RE.finditer(hlo_text):
        shape_txt, op, _start = m.group(1), m.group(2), m.group(3)
        b = _shape_bytes(shape_txt)
        s = stats.setdefault(op, {"bytes": 0.0, "count": 0})
        s["bytes"] += b
        s["count"] += 1
    return stats


def slstm_flops_correction(cfg: ModelConfig, shape: ShapeSpec) -> float:
    """The sLSTM time scan stays rolled (O(S) trips) — estimate the flops
    XLA's cost analysis misses: (trips-1) x body, body ~ recurrent einsum
    (2*B*H*hd*4hd) + ~30 elementwise ops on (B, 4d)."""
    if "slstm" not in cfg.pattern:
        return 0.0
    n_slstm = sum(1 for k in cfg.pattern if k == "slstm") * cfg.num_units
    B = shape.global_batch
    S = shape.seq_len if shape.kind != "decode" else 1
    if S <= 1:
        return 0.0
    d = cfg.d_model
    hd = d // cfg.num_heads
    body = 2.0 * B * cfg.num_heads * hd * 4 * hd + 30.0 * B * 4 * d
    mult = 3.0 if shape.kind == "train" else 1.0  # fwd + bwd(2x)
    return (S - 1) * body * n_slstm * mult


def active_param_count(cfg: ModelConfig) -> int:
    """Parameters touched per token (MoE: shared + top_k/E of routed)."""
    spec = model_spec_for(cfg)
    total = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(spec, is_leaf=_IS_SPEC)[0]:
        n = int(np.prod(leaf.shape))
        if "experts" in leaf.axes:
            n = int(n * cfg.top_k / max(cfg.num_experts, 1))
        total += n
    return total


# ---------------------------------------------------------------------------
# Cell runner
# ---------------------------------------------------------------------------


def _compile(cfg: ModelConfig, shape: ShapeSpec, mesh):
    fn, args, donate = build_step(cfg, shape, mesh)
    t0 = time.time()
    with activation_sharding(mesh):
        lowered = jax.jit(fn, donate_argnums=donate).lower(*args)
    t1 = time.time()
    compiled = lowered.compile()
    t2 = time.time()
    return compiled, round(t1 - t0, 2), round(t2 - t1, 2)


def _costs(compiled) -> Dict[str, float]:
    ca = compiled.cost_analysis()
    coll = collective_stats(compiled.as_text())
    out = {
        "flops": float(ca.get("flops", 0.0)),
        "bytes": float(ca.get("bytes accessed", 0.0)),
    }
    for k, v in coll.items():
        out[f"coll_{k}_bytes"] = v["bytes"]
        out[f"coll_{k}_count"] = v["count"]
    return out


def run_cell(arch: str, shape_name: str, multi_pod: bool, *, costs: bool = True) -> Dict[str, Any]:
    """One dry-run cell.

    Phase A — compile the FULL config with scan-over-layers: the required
    artifact (sharding coherence + per-device memory analysis).
    Phase B (single-pod only) — compile 1-unit and 2-unit depth variants
    with all inner scans UNROLLED, and extrapolate per-step costs affinely:
    cost(U) = a + b*U.  XLA's cost analysis counts loop bodies ONCE, so the
    full scanned artifact under-reports by ~num_units x; depth variants are
    exactly affine in U (embedding/loss/optimizer in `a`, per-unit compute,
    FSDP gathers and EP collectives in `b`).  The sLSTM time scan stays
    rolled even in phase B — corrected analytically.
    """
    cfg = get_config(arch)
    shape = next(s for s in SHAPES if s.name == shape_name)
    rec: Dict[str, Any] = {
        "arch": arch,
        "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
    }
    skip = shape_applicable(cfg, shape)
    if skip:
        rec["status"] = "skipped"
        rec["reason"] = skip
        return rec
    try:
        mesh = make_production_mesh(multi_pod=multi_pod)
        n_dev = int(np.prod(mesh.devices.shape))

        # ---- Phase A: full-config compile (the dry-run proof) -------------
        compiled, rec["lower_s"], rec["compile_s"] = _compile(cfg, shape, mesh)
        ma = compiled.memory_analysis()
        rec["mem"] = {
            "argument_bytes": int(ma.argument_size_in_bytes),
            "output_bytes": int(ma.output_size_in_bytes),
            "temp_bytes": int(ma.temp_size_in_bytes),
            "alias_bytes": int(ma.alias_size_in_bytes),
            "peak_bytes": int(ma.peak_memory_in_bytes),
        }
        # XLA:CPU's peak_memory only covers entry args; the honest per-device
        # residency bound is args + temps (fp32 grads, remat residuals, ...).
        resident = max(
            int(ma.peak_memory_in_bytes),
            int(ma.argument_size_in_bytes) + int(ma.temp_size_in_bytes),
        )
        rec["mem"]["resident_bytes"] = resident
        rec["fits_hbm"] = bool(resident <= HW.HBM_BYTES)
        rec["status"] = "ok"

        # ---- Phase B: affine cost extrapolation (roofline terms) ----------
        if costs and not multi_pod:
            U = cfg.num_units
            variants = {}
            for u in (1, 2):
                vcfg = reduced_units(cfg, u).replace(
                    scan_layers=False, unroll_scans=True
                )
                vc, _, _ = _compile(vcfg, shape, mesh)
                variants[u] = _costs(vc)
            keys = set(variants[1]) | set(variants[2])
            total: Dict[str, float] = {}
            for k in keys:
                c1 = variants[1].get(k, 0.0)
                c2 = variants[2].get(k, 0.0)
                b = max(c2 - c1, 0.0)
                a = max(c1 - b, 0.0)
                total[k] = a + b * U
            rec["cost_model"] = {"u1": variants[1], "u2": variants[2]}

            flops_dev = total["flops"]
            corr = slstm_flops_correction(cfg, shape) / n_dev
            if corr:
                rec["slstm_flops_correction_per_dev"] = corr
                flops_dev += corr
            bytes_dev = total["bytes"]
            coll_bytes = sum(
                v * (2.0 if k.startswith("coll_all-reduce") else 1.0)
                for k, v in total.items()
                if k.startswith("coll_") and k.endswith("_bytes")
            )
            rec["flops_per_dev"] = flops_dev
            rec["bytes_per_dev"] = bytes_dev
            rec["collectives"] = {
                k[5:-6]: {"bytes": v, "count": total.get(k[:-6] + "_count", 0)}
                for k, v in total.items()
                if k.startswith("coll_") and k.endswith("_bytes")
            }
            rec["collective_bytes"] = coll_bytes

            terms = {
                "compute_s": flops_dev / HW.PEAK_FLOPS_BF16,
                "memory_s": bytes_dev / HW.HBM_BW,
                "collective_s": coll_bytes / HW.ICI_BW,
            }
            rec["terms"] = terms
            rec["dominant"] = max(terms, key=terms.get)

            # MODEL_FLOPS: 6*N*D train, 2*N*D forward-only.
            n_active = active_param_count(cfg)
            tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode" else 1)
            mult = 6 if shape.kind == "train" else 2
            model_flops = mult * n_active * tokens
            rec["model_flops_total"] = float(model_flops)
            rec["model_flops_per_dev"] = float(model_flops / n_dev)
            rec["useful_flops_ratio"] = (
                float(model_flops / n_dev / flops_dev) if flops_dev else None
            )
            rec["params_total"] = param_count(model_spec_for(cfg))
            rec["params_active"] = n_active
    except Exception as e:  # noqa: BLE001 — every failure is a bug report
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-3000:]
    return rec


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--list", action="store_true")
    ap.add_argument("--resume", action="store_true",
                    help="skip cells whose output JSON already exists and is ok")
    args = ap.parse_args(argv)
    use_compile_cache()

    archs = ARCH_IDS if args.arch == "all" else [args.arch]
    shapes = [s.name for s in SHAPES] if args.shape == "all" else [args.shape]
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    if args.list:
        for a in archs:
            for s in shapes:
                print(a, s)
        return 0

    os.makedirs(args.out, exist_ok=True)
    failures = 0
    for a in archs:
        for s in shapes:
            for mp in meshes:
                tag = f"{a}_{s}_{'multi' if mp else 'single'}"
                path0 = os.path.join(args.out, tag + ".json")
                if args.resume and os.path.exists(path0):
                    try:
                        prev = json.load(open(path0))
                        if prev.get("status") in ("ok", "skipped") and (
                            mp or prev.get("status") == "skipped" or "terms" in prev
                        ):
                            print(f"[ resume] {tag}", flush=True)
                            continue
                    except Exception:
                        pass
                rec = run_cell(a, s, mp)
                path = os.path.join(args.out, tag + ".json")
                with open(path, "w") as f:
                    json.dump(rec, f, indent=1, default=str)
                status = rec["status"]
                extra = ""
                if status == "ok":
                    extra = f" resident={rec['mem']['resident_bytes']/2**30:.2f}GiB fits={rec['fits_hbm']}"
                    if "terms" in rec:
                        t = rec["terms"]
                        extra += (
                            f" comp={t['compute_s']*1e3:.2f}ms"
                            f" mem={t['memory_s']*1e3:.2f}ms"
                            f" coll={t['collective_s']*1e3:.2f}ms dom={rec['dominant']}"
                        )
                elif status == "error":
                    failures += 1
                    extra = " " + rec["error"][:120]
                elif status == "skipped":
                    extra = " " + rec["reason"][:60]
                print(f"[{status:>7}] {tag}{extra}", flush=True)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
