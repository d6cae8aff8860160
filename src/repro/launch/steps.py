"""Step factories: the sharded train / prefill / decode programs.

One factory per shape kind. Each returns a jitted function plus the
abstract (ShapeDtypeStruct) arguments needed to ``.lower()`` it — the
dry-run lowers these; train.py / serve.py call them with real arrays.

Sharding recipe (see DESIGN.md §5):
  params        TP over 'model' + FSDP over 'data' (per the ParamSet
                logical-axis table), layer axis unsharded (scanned)
  activations   batch over ('pod', 'data'); optional SP: seq over 'model'
  KV caches     seq over 'model' (flash decode) or kv-heads over 'model'
                (cross-attn), batch over ('pod', 'data'); divisibility-
                checked per leaf with automatic fallback to replication
  optimizer     moments inherit the param specs (match_opt_specs)

Per-cell deployment overrides (microbatching, SP, optimizer) live in
DEPLOY below — these are the §Perf knobs.
"""
from __future__ import annotations

from dataclasses import dataclass, replace

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.models.act_sharding import ActivationSharding, activation_sharding
from repro.models.common import SHAPES, ModelConfig, ShapeCfg
from repro.models.registry import ModelBundle, get_bundle
from repro.training import optimizer as opt_lib
from repro.training.optimizer import OptConfig
from repro.training.trainer import TrainConfig, make_train_step


# ---------------------------------------------------------------------------
# per-cell deployment config
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class DeployCfg:
    microbatches: int = -1           # -1 = auto: 1 sequence/device/microbatch
    seq_shard: bool = False          # SP on residuals
    optimizer: str = "adamw"
    compress_pods: bool = False
    straggler_masking: bool = False
    accum_dtype: str = "f32"         # "bf16" halves the grad-accum buffer
    lr: float = 3e-4
    # --- sharding-policy knobs (§Perf levers) ---
    # tp="none": small models drop tensor parallelism — the per-layer TP
    # activation all-reduces (the dominant collective for <4B models on
    # a 16-wide model axis) disappear; the model axis joins the batch
    # axes instead (pure DP x FSDP over all 256 chips).
    tp: str = "model"                # "model" | "none"
    # fsdp=False: decode cells keep weights TP-resident instead of
    # re-all-gathering FSDP shards every decoded token.
    fsdp: bool = True
    # fsdp_wide: shard params over (data, model) — for tp="none" models
    # whose params/moments don't fit a 16-way FSDP shard (yi-34b: the
    # 56-head layout doesn't divide a 16-wide TP axis at all, see §Perf)
    fsdp_wide: bool = False
    # serve in bf16 weights (standard inference practice; halves both
    # the weight residency and the weight-streaming bytes per token)
    serve_bf16: bool = False


# keyed by (arch, shape); fall back to (arch, None) then DEFAULT.
# Train cells auto-microbatch (1 seq/device/µb) so remat-saved
# activations fit; the wide models additionally run SP (seq -> model on
# residuals) and llama3-405b uses Adafactor (see DESIGN.md memory budget
# and EXPERIMENTS.md §Perf).
_SMALL_DENSE = ("granite-3-2b", "qwen1.5-0.5b", "mamba2-2.7b",
                "zamba2-2.7b", "whisper-medium")
# decode: weights stay TP-resident in bf16 wherever P_bf16/16 fits HBM
# (all but llama3-405b and qwen3-moe, whose decode keeps FSDP + bf16)
_DECODE_RESIDENT = ("yi-34b", "internvl2-76b", "granite-3-2b",
                    "qwen1.5-0.5b", "mamba2-2.7b", "zamba2-2.7b",
                    "whisper-medium", "qwen2-moe-a2.7b")

DEPLOY: dict = {
    ("llama3-405b", "train_4k"): DeployCfg(
        seq_shard=True, optimizer="adafactor", accum_dtype="bf16"),
    ("llama3-405b", None): DeployCfg(optimizer="adafactor", seq_shard=True),
    # NOTE: no SP on these train cells — their remat carries fit without
    # it (3-5 GiB/dev), and naive SP made GSPMD replicate f32 weights
    # per layer per microbatch (§Perf yi-34b iteration log). llama3-405b
    # keeps SP (carries 17 GiB) with the explicit matmul_in gathers.
    ("qwen3-moe-235b-a22b", "train_4k"): DeployCfg(accum_dtype="bf16"),
    ("internvl2-76b", "train_4k"): DeployCfg(accum_dtype="bf16"),
    # yi-34b: 56 q-heads / 8 kv-heads divide NOTHING on a 16-wide model
    # axis -> TP attention degenerates to replicated partial-sum ARs
    # (1.3 TiB/dev/step). Pure DP + (data x model) FSDP instead.
    ("yi-34b", "train_4k"): DeployCfg(tp="none", fsdp_wide=True,
                                      accum_dtype="bf16"),
    ("qwen3-moe-235b-a22b", "prefill_32k"): DeployCfg(seq_shard=True),
    ("internvl2-76b", "prefill_32k"): DeployCfg(seq_shard=True),
    ("yi-34b", "prefill_32k"): DeployCfg(seq_shard=True),
    ("llama3-405b", "prefill_32k"): DeployCfg(
        optimizer="adafactor", seq_shard=True),
    ("llama3-405b", "decode_32k"): DeployCfg(
        optimizer="adafactor", serve_bf16=True),
    ("qwen3-moe-235b-a22b", "decode_32k"): DeployCfg(serve_bf16=True),
}
# small dense/ssm models: TP=16 starves the MXU and drowns in per-layer
# activation all-reduces — train/prefill go pure DPxFSDP (§Perf iter 2);
# grad reduction in bf16 (§Perf iter 4)
for _a in _SMALL_DENSE:
    DEPLOY.setdefault((_a, "train_4k"),
                      DeployCfg(tp="none", accum_dtype="bf16"))
    DEPLOY.setdefault((_a, "prefill_32k"), DeployCfg(tp="none"))
# decode: drop per-token FSDP weight re-gathers + serve bf16 (§Perf
# yi-34b iterations 1-2)
for _a in _DECODE_RESIDENT:
    DEPLOY.setdefault((_a, "decode_32k"),
                      DeployCfg(fsdp=False, serve_bf16=True))
    DEPLOY.setdefault((_a, "long_500k"),
                      DeployCfg(fsdp=False, serve_bf16=True))
DEFAULT_DEPLOY = DeployCfg()


def deploy_for(arch: str, shape: str) -> DeployCfg:
    return DEPLOY.get((arch, shape),
                      DEPLOY.get((arch, None), DEFAULT_DEPLOY))


def resolve_deploy(dep: DeployCfg, shape: ShapeCfg, mesh) -> DeployCfg:
    """Make the deploy concrete for this (shape, mesh): auto microbatch
    count targets one sequence per device per microbatch, clamped to a
    divisor of the global batch."""
    mb = dep.microbatches
    if shape.kind != "train":
        mb = 1
    elif mb == -1:
        sizes = axis_sizes(mesh)
        axes = ("pod", "data", "model") if dep.tp == "none" \
            else ("pod", "data")
        shards = 1
        for a in axes:
            if a in sizes and shape.global_batch % (shards * sizes[a]) == 0:
                shards *= sizes[a]
        mb = max(shape.global_batch // shards, 1)
    while shape.global_batch % mb != 0:
        mb -= 1
    return replace(dep, microbatches=mb) if mb != dep.microbatches else dep


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------

def axis_sizes(mesh) -> dict:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def batch_axes_for(mesh, b: int, include_model: bool = False) -> tuple:
    """Greedy ('pod','data'[,'model']) prefix whose product divides b."""
    sizes = axis_sizes(mesh)
    axes = ("pod", "data", "model") if include_model else ("pod", "data")
    out, prod = [], 1
    for a in axes:
        if a in sizes and b % (prod * sizes[a]) == 0:
            out.append(a)
            prod *= sizes[a]
    return tuple(out)


def rules_for_deploy(mesh, dep: DeployCfg):
    """Mesh rules with the deploy's sharding policy applied."""
    from repro.models.common import rules_for_mesh
    rules = rules_for_mesh(mesh)
    kw = {}
    if dep.tp == "none":
        kw["tensor_axis"] = None
        kw["batch_axes"] = tuple(
            a for a in ("pod", "data", "model")
            if a in rules.mesh_axis_sizes)
    if dep.fsdp_wide:
        kw["fsdp_axis"] = tuple(
            a for a in ("data", "model") if a in rules.mesh_axis_sizes)
    if not dep.fsdp:
        kw["fsdp_axis"] = None
    return replace(rules, **kw) if kw else rules


def _ns(mesh, spec) -> NamedSharding:
    return NamedSharding(mesh, spec)


def _sharded_struct(mesh, spec, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=_ns(mesh, spec))


def param_tree(bundle: ModelBundle, mesh, rules):
    """(abstract params with shardings, specs dict)."""
    shapes = bundle.param_shapes()
    specs = bundle.param_specs(rules)
    abstract = {
        k: _sharded_struct(mesh, specs[k], v.shape, v.dtype)
        for k, v in shapes.items()
    }
    return abstract, specs


def batch_specs(cfg: ModelConfig, shape: ShapeCfg, mesh,
                include_model: bool = False) -> dict:
    """PartitionSpecs for every input_specs() leaf of a train/prefill cell."""
    bat = batch_axes_for(mesh, shape.global_batch, include_model)
    bspec = P(bat if bat else None, None)
    out = {"tokens": bspec, "labels": bspec}
    if cfg.family == "vlm":
        out["img_embeds"] = P(bat if bat else None, None, None)
    if cfg.family == "encdec":
        out["frames"] = P(bat if bat else None, None, None)
    return out


def cache_specs(cfg: ModelConfig, cache_shapes: dict, mesh, b: int) -> dict:
    """Per-leaf PartitionSpec for a KV/SSM cache pytree.

    Layouts (leading L/n_inv axis is scanned, never sharded):
      k, v     (L, B, S, KV, Dh)   batch x (seq -> model)   flash decode
      ck, cv   (L, B, Te, KV, Dh)  batch x (kv -> model)    cross-attn
      ssm      (L, B, H, P, N)     batch x (heads -> model)
      hx       (L, B, dc-1, Di)    batch x (channels -> model)
      hb, hc   (L, B, dc-1, N)     batch only (tiny)
      length   (B,)                batch
    """
    sizes = axis_sizes(mesh)
    tp = sizes.get("model", 1)
    bat = batch_axes_for(mesh, b)
    bat_p = bat if bat else None

    def spec_of(name: str, s) -> P:
        shp = s.shape
        if name == "length":
            return P(bat_p)
        if name in ("k", "v"):
            seq = "model" if shp[2] % tp == 0 else None
            return P(None, bat_p, seq, None, None)
        if name in ("ck", "cv"):
            kv = "model" if shp[3] % tp == 0 else None
            return P(None, bat_p, None, kv, None)
        if name == "ssm":
            h = "model" if shp[2] % tp == 0 else None
            return P(None, bat_p, h, None, None)
        if name == "hx":
            c = "model" if shp[3] % tp == 0 else None
            return P(None, bat_p, None, c)
        if name in ("hb", "hc"):
            return P(None, bat_p, None, None)
        return P(*([None] * len(shp)))

    return {k: spec_of(k, v) for k, v in cache_shapes.items()}


# ---------------------------------------------------------------------------
# train step
# ---------------------------------------------------------------------------

def build_train_step(bundle: ModelBundle, mesh, rules, dep: DeployCfg):
    """Returns (jitted_step, abstract_args tuple, meta dict)."""
    tcfg = TrainConfig(
        opt=OptConfig(name=dep.optimizer, lr=dep.lr),
        microbatches=dep.microbatches,
        compress_pods=dep.compress_pods,
        straggler_masking=dep.straggler_masking,
        accum_dtype=dep.accum_dtype,
    )
    # the pod axis is manual inside the compress/straggler shard_map, so
    # activation constraints there may only reference auto axes
    pod_manual = dep.compress_pods or dep.straggler_masking
    bat = tuple(a for a in rules.batch_axes
                if not (pod_manual and a == "pod"))
    act = ActivationSharding(
        batch_axes=bat, seq_axis="model" if dep.seq_shard else None)

    step = make_train_step(
        bundle, mesh, rules, tcfg,
        act_ctx=lambda: activation_sharding(act, mesh))

    params, specs = param_tree(bundle, mesh, rules)
    opt_specs = opt_lib.match_opt_specs(
        tcfg.opt, bundle.param_shapes(), specs)
    opt_abstract = jax.eval_shape(
        lambda: opt_lib.init_opt_state(tcfg.opt, bundle.param_shapes()))
    opt_state = jax.tree.map(
        lambda s, spec: _sharded_struct(mesh, spec, s.shape, s.dtype),
        opt_abstract, opt_specs,
        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))
    return step, (params, opt_state), tcfg


def train_batch_abstract(bundle: ModelBundle, shape: ShapeCfg, mesh,
                         include_model: bool = False) -> dict:
    cfg = bundle.cfg
    ispecs = bundle.input_specs(shape)
    pspecs = batch_specs(cfg, shape, mesh, include_model=include_model)
    return {k: _sharded_struct(mesh, pspecs[k], v.shape, v.dtype)
            for k, v in ispecs.items()}


# ---------------------------------------------------------------------------
# prefill step
# ---------------------------------------------------------------------------

def build_prefill_step(bundle: ModelBundle, mesh, rules, shape: ShapeCfg,
                       dep: DeployCfg):
    cfg = bundle.cfg
    act = ActivationSharding(
        batch_axes=rules.batch_axes,
        seq_axis="model" if dep.seq_shard else None)
    params, _specs = param_tree(bundle, mesh, rules)
    batch = train_batch_abstract(bundle, shape, mesh,
                                 include_model=(dep.tp == "none"))
    batch.pop("labels", None)

    b = shape.global_batch
    cshapes = bundle.cache_shapes(b, shape.seq_len)
    cspecs = cache_specs(cfg, cshapes, mesh, b)
    bat = batch_axes_for(mesh, b)
    logits_spec = P(bat if bat else None,
                    "model" if cfg.vocab % axis_sizes(mesh).get(
                        "model", 1) == 0 else None)

    def step(params, batch):
        with activation_sharding(act, mesh):
            cache, logits = bundle.prefill(params, batch,
                                           max_len=shape.seq_len, mesh=mesh)
        return cache, logits

    jitted = jax.jit(
        step,
        out_shardings=(
            jax.tree.map(lambda s: _ns(mesh, s), cspecs,
                         is_leaf=lambda x: isinstance(x, P)),
            _ns(mesh, logits_spec),
        ),
    )
    return jitted, (params, batch)


# ---------------------------------------------------------------------------
# decode step
# ---------------------------------------------------------------------------

def build_decode_step(bundle: ModelBundle, mesh, rules, shape: ShapeCfg,
                      dep: DeployCfg):
    cfg = bundle.cfg
    params, _specs = param_tree(bundle, mesh, rules)
    b = shape.global_batch
    cshapes = bundle.cache_shapes(b, shape.seq_len)
    cspecs = cache_specs(cfg, cshapes, mesh, b)
    cache = jax.tree.map(
        lambda s, spec: _sharded_struct(mesh, spec, s.shape, s.dtype),
        cshapes, cspecs, is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))
    bat = batch_axes_for(mesh, b)
    token = _sharded_struct(mesh, P(bat if bat else None, None),
                            (b, 1), jnp.int32)
    logits_spec = P(bat if bat else None,
                    "model" if cfg.vocab % axis_sizes(mesh).get(
                        "model", 1) == 0 else None)

    def step(params, cache, token):
        return bundle.decode_step(params, cache, token, mesh=mesh)

    jitted = jax.jit(
        step,
        out_shardings=(
            jax.tree.map(lambda s: _ns(mesh, s), cspecs,
                         is_leaf=lambda x: isinstance(x, P)),
            _ns(mesh, logits_spec),
        ),
        donate_argnums=(1,),
    )
    return jitted, (params, cache, token)


# ---------------------------------------------------------------------------
# cell driver (used by dryrun.py and benchmarks)
# ---------------------------------------------------------------------------

def applicable(cfg: ModelConfig, shape_name: str) -> tuple[bool, str]:
    """Whether an (arch, shape) cell runs, and why not if it doesn't."""
    if shape_name == "long_500k" and not cfg.subquadratic:
        return False, ("full-attention arch: 500k decode needs a "
                       "sub-quadratic path (DESIGN.md §6)")
    return True, ""


def lower_cell(arch_cfg: ModelConfig, shape_name: str, mesh,
               dep: DeployCfg | None = None, shapes: dict | None = None):
    """Build + lower one (arch x shape x mesh) cell. Returns ``lowered``."""
    from repro.models.common import rules_for_mesh

    shapes = shapes or SHAPES
    shape = shapes[shape_name]
    dep = dep or deploy_for(arch_cfg.name, shape_name)
    dep = resolve_deploy(dep, shape, mesh)
    if dep.serve_bf16 and shape.kind in ("prefill", "decode"):
        arch_cfg = arch_cfg.replace(param_dtype=jnp.bfloat16)
    bundle = get_bundle(arch_cfg)
    rules = rules_for_deploy(mesh, dep)

    with jax.set_mesh(mesh):
        if shape.kind == "train":
            step, (params, opt_state), _ = build_train_step(
                bundle, mesh, rules, dep)
            batch = train_batch_abstract(
                bundle, shape, mesh, include_model=(dep.tp == "none"))
            if dep.compress_pods or dep.straggler_masking:
                ef = jax.tree.map(
                    lambda p: jax.ShapeDtypeStruct(p.shape, jnp.float32),
                    params)
                n_pods = axis_sizes(mesh).get("pod", 1)
                health = jax.ShapeDtypeStruct((n_pods,), jnp.float32)
                return step.lower(params, opt_state, batch, ef, health)
            return step.lower(params, opt_state, batch)
        if shape.kind == "prefill":
            jitted, (params, batch) = build_prefill_step(
                bundle, mesh, rules, shape, dep)
            return jitted.lower(params, batch)
        # decode
        jitted, (params, cache, token) = build_decode_step(
            bundle, mesh, rules, shape, dep)
        return jitted.lower(params, cache, token)
