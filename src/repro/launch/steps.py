"""Step builders + abstract input specs for every (arch x input-shape).

``build(arch_id, shape_name, mesh)`` returns a ``StepSpec`` bundling the
step function, abstract (ShapeDtypeStruct) arguments — weak-type-correct
and shardable, no device allocation — and in/out shardings.  This is the
single entry point used by the dry-run, the roofline analysis, and the
integration tests (which call it on a small host-device mesh).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import configs as config_lib
from repro.configs.base import DiTConfig, ModelConfig
from repro.models import blocks, common, dit, encdec, transformer
from repro.optim import adamw
from repro.sharding import partitioning as pt


@dataclasses.dataclass
class StepSpec:
    name: str
    fn: Callable
    args: Tuple[Any, ...]
    in_shardings: Tuple[Any, ...]
    out_shardings: Any
    donate_argnums: Tuple[int, ...] = ()
    static_argnums: Tuple[int, ...] = ()


def _abstract_opt_state(params_abs):
    zeros_like = lambda p: jax.ShapeDtypeStruct(p.shape, jnp.float32)
    return adamw.OptState(
        mu=jax.tree.map(zeros_like, params_abs),
        nu=jax.tree.map(zeros_like, params_abs),
        step=jax.ShapeDtypeStruct((), jnp.int32))


def _replicated(mesh):
    return NamedSharding(mesh, P())


def model_specs(cfg: ModelConfig):
    if cfg.is_encdec:
        return encdec.encdec_specs(cfg)
    return transformer.lm_specs(cfg)


def _cache_shardings(cfg: ModelConfig, mesh: Mesh, rules, global_batch: int):
    dp = pt.dp_axes(mesh)
    dpsz = pt._axis_size(mesh, dp)
    cache_rules = dict(rules)
    cache_rules["layer"] = None
    if global_batch % dpsz == 0 and global_batch >= dpsz:
        cache_rules["batch"] = dp
        cache_rules["len"] = None
    else:
        # single-request long-context: shard the KV length instead
        cache_rules["batch"] = None
        cache_rules["len"] = "data"
    axes = blocks.stack_cache_axes(cfg)
    return jax.tree.map(
        lambda a: NamedSharding(mesh, pt.spec_for_axes(a, cache_rules)),
        axes, is_leaf=lambda x: isinstance(x, tuple) and
        all(isinstance(e, (str, type(None))) for e in x))


# ---------------------------------------------------------------------------
# step functions
# ---------------------------------------------------------------------------

def activation_constrain(mesh: Optional[Mesh], mode: str = "serve",
                         seq_len: int = 0):
    """Pin [B, S, D] activations between blocks.

    serve: batch on dp only.  train: additionally shard the SEQUENCE dim
    on "model" (Megatron sequence parallelism) — the layer-scan carry is
    what remat stores per layer, and for a 126-layer 405B config an
    unsharded d_model carry alone is ~270 GB/device.  GSPMD turns the
    constraint into the standard SP all-gather before attention/FFN and
    reduce-scatter after.
    """
    if mesh is None:
        return None
    seq_entry = None
    if mode == "train" and seq_len and seq_len % mesh.shape["model"] == 0:
        seq_entry = "model"
    spec = P(pt.dp_axes(mesh), seq_entry, None)

    def constrain(t):
        if t.ndim == 3:
            return jax.lax.with_sharding_constraint(
                t, NamedSharding(mesh, spec))
        return t
    return constrain


def make_train_step(cfg: ModelConfig, opt_cfg: Optional[adamw.AdamWConfig]
                    = None, mesh: Optional[Mesh] = None, seq_len: int = 0,
                    microbatch: int = 1):
    """``microbatch > 1`` = gradient accumulation: the global batch is
    split into ``microbatch`` sequential sub-batches inside one jitted
    step (lax.scan), dividing peak activation memory by the same factor
    at unchanged math (§Perf memory iteration for the >=100B trains)."""
    opt_cfg = opt_cfg or adamw.AdamWConfig(
        moment_dtype="bfloat16" if pt.param_bytes(cfg) > 2e11 else "float32")
    loss = encdec.loss_fn if cfg.is_encdec else transformer.loss_fn
    constrain = activation_constrain(mesh, "train", seq_len)
    constrain_ffn = None
    if mesh is not None and cfg.d_ff % mesh.shape["model"] == 0:
        ffn_spec = P(pt.dp_axes(mesh), None, "model")

        def constrain_ffn(t):  # noqa: F811 — Megatron-SP TP switch
            return jax.lax.with_sharding_constraint(
                t, NamedSharding(mesh, ffn_spec))

    # REFUTED (§Perf A5): pinning q to a head-sharded layout the same way
    # regressed collectives 17->39 TB/dev on llama3-405b train — GSPMD
    # inserts an S->H reshard before RoPE and back inside every layer;
    # the FFN hook alone is the right Megatron-SP boundary.
    constrain_heads = None

    def grads_of(params, batch):
        return jax.value_and_grad(
            lambda p: loss(p, batch, cfg, constrain=constrain,
                           constrain_ffn=constrain_ffn,
                           constrain_heads=constrain_heads),
            has_aux=True)(params)

    def train_step(params, opt_state, batch):
        if microbatch > 1:
            mb = jax.tree.map(
                lambda x: x.reshape((microbatch, x.shape[0] // microbatch)
                                    + x.shape[1:]), batch)

            def body(acc, one):
                (l, metrics), grads = grads_of(params, one)
                acc = jax.tree.map(
                    lambda a, g: a + g.astype(jnp.float32) / microbatch,
                    acc, grads)
                return acc, metrics

            zeros = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            grads, metrics = jax.lax.scan(body, zeros, mb)
            metrics = jax.tree.map(lambda m: jnp.mean(m), metrics)
        else:
            (l, metrics), grads = grads_of(params, batch)
        new_params, new_opt, om = adamw.update(opt_cfg, grads, opt_state,
                                               params)
        metrics = dict(metrics)
        metrics.update(om)
        return new_params, new_opt, metrics

    return train_step, opt_cfg


def make_prefill_step(cfg: ModelConfig, mesh: Optional[Mesh] = None,
                      seq_len: int = 0):
    """Prefill: full-sequence forward, last-token logits only (the
    [B, S, vocab] tensor must never materialise at 32k).  Sequence
    parallel like train — prefill is the same forward."""
    constrain = activation_constrain(mesh, "train", seq_len) or (
        lambda t: t)

    def prefill_step(params, batch):
        if cfg.is_encdec:
            memory = encdec.encode(params, batch["frames"], cfg,
                                   constrain=constrain)
            x = common.embed(params["embed"],
                             batch["tokens"]).astype(jnp.dtype(cfg.dtype))

            def body(h, layer_params):
                h, _ = encdec._dec_block(layer_params, h, memory, cfg)
                return constrain(h), ()
            h, _ = jax.lax.scan(body, constrain(x), params["decoder"])
            hn = common.rmsnorm(params["final_norm"], h[:, -1:], cfg.norm_eps)
            return (hn @ params["head"]["kernel"].astype(hn.dtype))[:, 0]
        x = common.embed(params["embed"],
                         batch["tokens"]).astype(jnp.dtype(cfg.dtype))
        if cfg.n_prefix_tokens > 0:
            pe = common.dense(params["prefix_proj"],
                              batch["prefix_embeds"].astype(x.dtype))
            x = jnp.concatenate([pe, x], axis=1)
        h, _ = blocks.stack_full(params["stack"], x, cfg, remat=False,
                                 constrain=constrain)
        hn = common.rmsnorm(params["final_norm"], h[:, -1:], cfg.norm_eps)
        w = transformer._embedding_matrix(params, cfg)
        return (hn @ w.astype(hn.dtype))[:, 0]
    return prefill_step


def make_decode_step(cfg: ModelConfig, window: int = 0):
    if cfg.is_encdec:
        def decode_step(params, tokens, cache, memory):
            logits, new_cache = encdec.decode_step(params, tokens, memory,
                                                   cache, cfg, window=window)
            return logits, new_cache
        return decode_step

    def decode_step(params, tokens, cache):
        logits, new_cache = transformer.decode_step(params, tokens, cache,
                                                    cfg, window=window)
        return logits, new_cache
    return decode_step


# ---------------------------------------------------------------------------
# abstract inputs per (arch, shape)
# ---------------------------------------------------------------------------

def input_specs(cfg: ModelConfig, shape_name: str) -> Dict[str, Any]:
    """Abstract model inputs for a named input shape (no allocation)."""
    info = config_lib.INPUT_SHAPES[shape_name]
    seq, gb, kind = info["seq_len"], info["global_batch"], info["kind"]
    dtype = jnp.dtype(cfg.dtype)
    i32 = jnp.int32

    if kind in ("train", "prefill"):
        if cfg.is_encdec:
            batch = {
                "frames": jax.ShapeDtypeStruct((gb, seq, cfg.d_model), dtype),
                "tokens": jax.ShapeDtypeStruct((gb, seq), i32),
            }
        elif cfg.n_prefix_tokens > 0:
            text = seq - cfg.n_prefix_tokens
            batch = {
                "prefix_embeds": jax.ShapeDtypeStruct(
                    (gb, cfg.n_prefix_tokens, cfg.d_model), dtype),
                "tokens": jax.ShapeDtypeStruct((gb, text), i32),
            }
        else:
            batch = {"tokens": jax.ShapeDtypeStruct((gb, seq), i32)}
        if kind == "train":
            lab = batch["tokens"].shape[1] if not cfg.is_encdec else seq
            batch["labels"] = jax.ShapeDtypeStruct((gb, lab), i32)
        return batch

    assert kind == "decode"
    out = {"tokens": jax.ShapeDtypeStruct((gb, 1), i32),
           "cache": blocks.stack_cache_abstract(cfg, gb, seq, dtype)}
    if cfg.is_encdec:
        out["memory"] = jax.ShapeDtypeStruct((gb, seq, cfg.d_model), dtype)
    return out


def _batch_shardings(batch_abs, mesh: Mesh, gb: int):
    def one(x):
        return pt.batch_spec(mesh, gb, len(x.shape))
    return jax.tree.map(one, batch_abs)


def build(arch_id: str, shape_name: str, mesh: Mesh,
          overrides: Optional[Dict[str, Any]] = None) -> StepSpec:
    """Assemble (fn, abstract args, shardings) for one dry-run combo.

    ``overrides`` (perf iterations): microbatch=int, moe_impl=str,
    serve_tp_gb=float.
    """
    ov = overrides or {}
    base_cfg = config_lib.get_config(arch_id)
    assert isinstance(base_cfg, ModelConfig), \
        f"{arch_id} is a DiT config; use build_dit()"
    cfg = config_lib.for_shape(base_cfg, shape_name)
    if cfg.moe is not None and (ov.get("moe_impl") or ov.get("moe_pad")):
        moe_kw = {}
        if ov.get("moe_impl"):
            moe_kw["impl"] = ov["moe_impl"]
        if ov.get("moe_pad"):
            moe_kw["padded_experts"] = int(ov["moe_pad"])
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, **moe_kw))
    info = config_lib.INPUT_SHAPES[shape_name]
    gb, kind = info["global_batch"], info["kind"]
    mode = "train" if kind == "train" else "serve"
    rules = pt.model_rules(cfg, mesh, mode,
                           serve_tp_bytes=float(
                               ov.get("serve_tp_gb", 4.0)) * 1e9,
                           shape_kind=kind)

    specs = model_specs(cfg)
    params_abs = common.abstract_params(specs, jnp.dtype(cfg.dtype))
    params_sh = pt.shardings_for_specs(specs, rules, mesh)

    if kind == "train":
        fn, opt_cfg = make_train_step(cfg, mesh=mesh,
                                      seq_len=info["seq_len"],
                                      microbatch=int(ov.get("microbatch",
                                                            1)))
        batch_abs = input_specs(cfg, shape_name)
        opt_abs = adamw.OptState(
            mu=jax.tree.map(lambda p: jax.ShapeDtypeStruct(
                p.shape, jnp.dtype(opt_cfg.moment_dtype)), params_abs),
            nu=jax.tree.map(lambda p: jax.ShapeDtypeStruct(
                p.shape, jnp.dtype(opt_cfg.moment_dtype)), params_abs),
            step=jax.ShapeDtypeStruct((), jnp.int32))
        opt_sh = adamw.OptState(mu=params_sh, nu=params_sh,
                                step=_replicated(mesh))
        batch_sh = _batch_shardings(batch_abs, mesh, gb)
        metrics_sh = _replicated(mesh)
        return StepSpec(
            name=f"{arch_id}:{shape_name}:train",
            fn=fn, args=(params_abs, opt_abs, batch_abs),
            in_shardings=(params_sh, opt_sh, batch_sh),
            out_shardings=(params_sh, opt_sh, None),
            donate_argnums=(0, 1))

    if kind == "prefill":
        fn = make_prefill_step(cfg, mesh=mesh, seq_len=info["seq_len"])
        batch_abs = input_specs(cfg, shape_name)
        batch_sh = _batch_shardings(batch_abs, mesh, gb)
        return StepSpec(
            name=f"{arch_id}:{shape_name}:prefill",
            fn=fn, args=(params_abs, batch_abs),
            in_shardings=(params_sh, batch_sh),
            out_shardings=None)

    # decode
    window = cfg.sliding_window
    seq = info["seq_len"]
    cache_len = min(seq, window) if window > 0 else seq
    fn = make_decode_step(cfg, window=window)
    ins = input_specs(cfg, shape_name)
    if cfg.is_encdec:
        cache_abs = encdec.decode_cache_abstract(cfg, gb, cache_len,
                                                 jnp.dtype(cfg.dtype))
        dp = pt.dp_axes(mesh)
        dpsz = pt._axis_size(mesh, dp)
        cache_rules = dict(rules)
        if gb % dpsz == 0 and gb >= dpsz:
            cache_rules.update({"layer": None, "batch": dp, "len": None})
        else:
            cache_rules.update({"layer": None, "batch": None,
                                "len": "data"})
        axes = blocks.attention.KVCache(
            k=("layer", "batch", "len", "kv_heads", "kv_head_dim"),
            v=("layer", "batch", "len", "kv_heads", "kv_head_dim"),
            index=("layer",))
        cache_sh = jax.tree.map(
            lambda a: NamedSharding(mesh, pt.spec_for_axes(a, cache_rules)),
            axes, is_leaf=lambda x: isinstance(x, tuple) and
            all(isinstance(e, (str, type(None))) for e in x))
    else:
        cache_abs = blocks.stack_cache_abstract(cfg, gb, cache_len,
                                                jnp.dtype(cfg.dtype))
        cache_sh = _cache_shardings(cfg, mesh, rules, gb)
    tok_sh = pt.batch_spec(mesh, gb, 2)
    args = [params_abs, ins["tokens"], cache_abs]
    in_sh = [params_sh, tok_sh, cache_sh]
    if cfg.is_encdec:
        args.append(ins["memory"])
        in_sh.append(pt.batch_spec(mesh, gb, 3))
    return StepSpec(
        name=f"{arch_id}:{shape_name}:decode",
        fn=fn, args=tuple(args), in_shardings=tuple(in_sh),
        out_shardings=(None, cache_sh),
        donate_argnums=(2,))


def build_dit(arch_id: str, mesh: Mesh, batch: int = 64,
              latent: int = 128, cached_step: bool = False) -> StepSpec:
    """Dry-run spec for the paper's own MMDiT.

    ``cached_step=False``: one full denoiser forward (the activated
    step).  ``cached_step=True``: the FreqCa skip path — band
    reconstruction from the cache + the final layer only — so the
    roofline of the step the paper makes ~N-1 of every N can be compared
    against the full one.
    """
    cfg = config_lib.get_config(arch_id)
    assert isinstance(cfg, DiTConfig)
    rules = pt.dit_rules(cfg, mesh)
    specs = dit.dit_specs(cfg)
    dtype = jnp.dtype(cfg.dtype)
    params_abs = common.abstract_params(specs, dtype)
    params_sh = pt.shardings_for_specs(specs, rules, mesh)
    n_tok = (latent // cfg.patch_size) ** 2
    t = jax.ShapeDtypeStruct((batch,), jnp.float32)
    if cached_step:
        from repro.core import cache as cache_lib
        from repro.core.policies import FreqCaPolicy
        pol = FreqCaPolicy(interval=5, method="dct", rho=0.0625,
                           high_order=2)
        feat = (batch, n_tok, cfg.d_model)
        state_abs = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
            cache_lib.init_state(pol, feat, dtype))
        dp = pt.dp_axes(mesh)
        state_sh = jax.tree.map(
            lambda a: NamedSharding(
                mesh, P(None, dp, *([None] * (len(a.shape) - 2))))
            if len(a.shape) >= 2 else NamedSharding(mesh, P()), state_abs)

        def fn(params, state, tt):
            crf_hat = cache_lib.predict(pol, state, tt[0])
            return dit.dit_from_crf(params, crf_hat, tt, cfg, latent,
                                    latent)
        return StepSpec(name=f"{arch_id}:cached_step", fn=fn,
                        args=(params_abs, state_abs, t),
                        in_shardings=(params_sh, state_sh,
                                      pt.batch_spec(mesh, batch, 1)),
                        out_shardings=None)
    lat = jax.ShapeDtypeStruct((batch, latent, latent, cfg.in_channels),
                               dtype)
    args = [params_abs, lat, t]
    in_sh = [params_sh, pt.batch_spec(mesh, batch, 4),
             pt.batch_spec(mesh, batch, 1)]
    if cfg.text_dim > 0:
        args.append(jax.ShapeDtypeStruct(
            (batch, cfg.n_text_tokens, cfg.text_dim), dtype))
        in_sh.append(pt.batch_spec(mesh, batch, 3))

        def fn(params, latents, tt, text):
            out = dit.dit_forward(params, latents, tt, cfg, text)
            return out.velocity, out.crf
    else:
        def fn(params, latents, tt):
            out = dit.dit_forward(params, latents, tt, cfg)
            return out.velocity, out.crf
    return StepSpec(name=f"{arch_id}:denoise", fn=fn, args=tuple(args),
                    in_shardings=tuple(in_sh), out_shardings=None)
