"""Diffusion transformers — the paper's model family.

Two denoisers:

* ``dit_*`` — FLUX-like MMDiT: optional dual-stream (image+text) "double"
  blocks followed by single-stream joint blocks, AdaLN-zero modulation,
  rectified-flow velocity output.  ``dit_forward`` returns the Cumulative
  Residual Feature (CRF) of the image stream next to the velocity, and
  ``dit_from_crf`` maps a *predicted* CRF straight to a velocity — the
  FreqCa skip path (everything but the final layer is bypassed).  A
  FLUX configuration (``vec_in_dim``, ``guidance_embed``, ``rope_axes``)
  takes FLUX's conditioning: ``vec`` = time + guidance + pooled-text
  embeddings, T5 tokens through ``text_proj`` into the dual-stream
  blocks, 3-axis RoPE on q and k, and (FLUX.1-Kontext) reference-image
  tokens joined after the generated ones; the CRF and the velocity
  cover the generated tokens only.

* ``backbone_*`` — wraps any assigned ``ModelConfig`` architecture
  (dense/MoE/SSM/hybrid) as a continuous-latent denoiser: patchify +
  time-conditioning around its residual stack.  This is how FreqCa is
  exercised on the assigned architectures (DESIGN.md §4).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import DiTConfig, ModelConfig
from repro.kernels import ops
from repro.models import attention, blocks, common
from repro.models.common import ParamSpec


class DenoiserOutput(NamedTuple):
    velocity: jnp.ndarray      # [B, H, W, C]
    crf: jnp.ndarray           # [B, S_img, d] image-stream CRF


def timestep_embedding(t: jnp.ndarray, dim: int, max_period: float = 10000.0):
    """t: [B] in [0, 1] -> [B, dim] sinusoidal features."""
    half = dim // 2
    freqs = jnp.exp(-math.log(max_period)
                    * jnp.arange(half, dtype=jnp.float32) / half)
    args = t.astype(jnp.float32)[:, None] * 1000.0 * freqs[None]
    return jnp.concatenate([jnp.cos(args), jnp.sin(args)], axis=-1)


def _pos_embedding(s: int, d: int):
    pos = jnp.arange(s, dtype=jnp.float32)[:, None]
    i = jnp.arange(d // 2, dtype=jnp.float32)[None]
    angles = pos / jnp.power(10000.0, 2 * i / d)
    return jnp.concatenate([jnp.sin(angles), jnp.cos(angles)], -1)


def patchify(latents: jnp.ndarray, p: int):
    b, h, w, c = latents.shape
    x = latents.reshape(b, h // p, p, w // p, p, c)
    return x.transpose(0, 1, 3, 2, 4, 5).reshape(b, (h // p) * (w // p),
                                                 p * p * c)


def unpatchify(tokens: jnp.ndarray, h: int, w: int, p: int, c: int):
    b = tokens.shape[0]
    x = tokens.reshape(b, h // p, w // p, p, p, c)
    return x.transpose(0, 1, 3, 2, 4, 5).reshape(b, h, w, c)


# ---------------------------------------------------------------------------
# MMDiT blocks
# ---------------------------------------------------------------------------

def _attn_specs(d: int, n_heads: int):
    hd = d // n_heads
    return {
        "wq": ParamSpec((d, n_heads, hd), ("embed", "heads", "head_dim")),
        "wk": ParamSpec((d, n_heads, hd), ("embed", "heads", "head_dim")),
        "wv": ParamSpec((d, n_heads, hd), ("embed", "heads", "head_dim")),
        "wo": ParamSpec((n_heads, hd, d), ("heads", "head_dim", "embed")),
        "q_norm": ParamSpec((hd,), (None,), init="ones"),
        "k_norm": ParamSpec((hd,), (None,), init="ones"),
    }


def _mlp_specs(d: int, f: int):
    return {"wi": ParamSpec((d, f), ("embed", "ffn")),
            "wo": ParamSpec((f, d), ("ffn", "embed"))}


def _mod_specs(d: int, n: int):
    return {"kernel": ParamSpec((d, n * d), ("embed", None), init="zeros"),
            "bias": ParamSpec((n * d,), (None,), init="zeros")}


def _modulation(params, cond, n: int):
    """cond: [B, d] -> n chunks of [B, 1, d]."""
    m = jax.nn.silu(cond) @ params["kernel"].astype(cond.dtype) \
        + params["bias"].astype(cond.dtype)
    return jnp.split(m[:, None, :], n, axis=-1)


def _qkv_heads(p, x, n_heads):
    b, s, d = x.shape
    hd = d // n_heads
    def norm(v, scale):
        return common.layernorm(v, scale=scale)
    q = norm((x @ p["wq"].astype(x.dtype).reshape(d, d)).reshape(b, s, n_heads, hd),
             p["q_norm"])
    k = norm((x @ p["wk"].astype(x.dtype).reshape(d, d)).reshape(b, s, n_heads, hd),
             p["k_norm"])
    v = (x @ p["wv"].astype(x.dtype).reshape(d, d)).reshape(b, s, n_heads, hd)
    return q, k, v


def _fuses_qk(rope, hd: int) -> bool:
    """Whether q and k go from their projections to flash's layout in one
    kernel pass (``ops.qk_norm_rope``): with RoPE tables, at a head size
    of whole 128 lanes, on the Pallas backend.  Elsewhere the block runs
    ``_qkv_heads`` then ``apply_rope``."""
    from repro.kernels import flash_attention as fa
    return rope is not None and hd % fa.LANES == 0 and ops.use_pallas()


def _joint_qkv(attns, xs, n_heads, rope):
    """q, k, v [B, S, H, hd] of the streams ``xs`` [B, S_i, d] joined
    along the tokens, each through its own ``attns`` weights and q/k
    norms; q and k normed and rotated by the one-pass kernel, which
    writes the joined sequence itself (``_fuses_qk``)."""
    b, _, d = xs[0].shape
    def project(w, x):
        return x @ w.astype(x.dtype).reshape(d, d)
    q, k = (ops.qk_norm_rope([project(p[w], x) for p, x in zip(attns, xs)],
                             [p[norm] for p in attns], rope, n_heads)
            for w, norm in (("wq", "q_norm"), ("wk", "k_norm")))
    vs = [project(p["wv"], x).reshape(b, x.shape[1], n_heads, d // n_heads)
          for p, x in zip(attns, xs)]
    v = vs[0] if len(vs) == 1 else jnp.concatenate(vs, axis=1)
    return q, k, v


# ---------------------------------------------------------------------------
# FLUX's 3-axis RoPE (``EmbedND``)
# ---------------------------------------------------------------------------

def token_ids(s_txt: int, grid: tuple, ref_grid: Optional[tuple] = None):
    """[S, 3] (index, row, column) ids of the joint sequence [text |
    image | reference]: text (0, 0, 0), the generated image (0, h, w) and
    the reference image (1, h, w), FLUX.1-Kontext's ``prepare_kontext``."""
    def image(index, hp, wp):
        rows, cols = np.meshgrid(np.arange(hp), np.arange(wp), indexing="ij")
        return np.stack([np.full(hp * wp, index), rows.ravel(),
                         cols.ravel()], -1)
    parts = [np.zeros((s_txt, 3), np.int64), image(0, *grid)]
    if ref_grid is not None:
        parts.append(image(1, *ref_grid))
    return np.concatenate(parts)


def rope_tables(ids, axes: Tuple[int, ...], theta: float):
    """(cos, sin), each [S, sum(axes) / 2] in f32: per axis i, the angles
    ``id_i · theta^(-2j / axes_i)``, axes concatenated."""
    angles = []
    for i, dim in enumerate(axes):
        omega = 1.0 / theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32)
                                / dim)
        angles.append(jnp.asarray(ids[:, i:i + 1], jnp.float32) * omega)
    ang = jnp.concatenate(angles, -1)
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x, rope):
    """Rotate each adjacent channel pair (2j, 2j+1) of x [B, S, H, hd] by
    the token's angle j (FLUX's ``apply_rope``)."""
    if rope is None:
        return x
    cos, sin = (a[None, :, None, :] for a in rope)
    b, s, h, hd = x.shape
    xf = x.astype(jnp.float32).reshape(b, s, h, hd // 2, 2)
    x0, x1 = xf[..., 0], xf[..., 1]
    out = jnp.stack([x0 * cos - x1 * sin, x0 * sin + x1 * cos], axis=-1)
    return out.reshape(b, s, h, hd).astype(x.dtype)


# flash-kernel threshold: below this, full-logits attention is cheaper
# than the kernel's tiling overhead (cf. attention._BLOCKWISE_MIN_SEQ)
_FLASH_MIN_SEQ = 1024


def _flash_ok(s: int) -> bool:
    from repro.kernels import flash_attention as fa
    return s >= _FLASH_MIN_SEQ and fa.dispatch_ok(s)


def _attention(q, k, v):
    """Joint attention over all S tokens: [B, S, H, hd] -> [B, S, H, hd]."""
    b, s, nh, hd = q.shape
    if ops.use_pallas() and _flash_ok(s):
        # non-causal flash attention: logits tiles stay in VMEM instead
        # of materialising the [B, H, S, S] tensor (q_per_kv=1 — the
        # joint streams share full MHA)
        return ops.flash(q, k, v, 1, causal=False)
    logits = jnp.einsum("bshk,bthk->bhst", q.astype(jnp.float32),
                        k.astype(jnp.float32)) / math.sqrt(hd)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    return jnp.einsum("bhst,bthk->bshk", probs, v)


def _project_out(o, p_out, x_dtype):
    return jnp.einsum("bshk,hkd->bsd", o, p_out.astype(x_dtype))


def _joint_attention(q, k, v, p_out, x_dtype):
    return _project_out(_attention(q, k, v), p_out, x_dtype)


def single_block_specs(cfg: DiTConfig):
    return {"mod": _mod_specs(cfg.d_model, 6),
            "attn": _attn_specs(cfg.d_model, cfg.n_heads),
            "mlp": _mlp_specs(cfg.d_model, cfg.d_ff)}


def single_block(params, x, cond, cfg: DiTConfig, rope=None):
    """Single-stream joint block with AdaLN-zero."""
    sh1, sc1, g1, sh2, sc2, g2 = _modulation(params["mod"], cond, 6)
    h = common.layernorm(x, cfg.norm_eps) * (1 + sc1) + sh1
    if _fuses_qk(rope, cfg.d_model // cfg.n_heads):
        q, k, v = _joint_qkv([params["attn"]], [h], cfg.n_heads, rope)
    else:
        q, k, v = _qkv_heads(params["attn"], h, cfg.n_heads)
        q, k = apply_rope(q, rope), apply_rope(k, rope)
    x = x + g1 * _joint_attention(q, k, v, params["attn"]["wo"], x.dtype)
    h = common.layernorm(x, cfg.norm_eps) * (1 + sc2) + sh2
    y = jax.nn.gelu(h @ params["mlp"]["wi"].astype(x.dtype))
    x = x + g2 * (y @ params["mlp"]["wo"].astype(x.dtype))
    return x


def double_block_specs(cfg: DiTConfig):
    return {"img": single_block_specs(cfg), "txt": single_block_specs(cfg)}


def double_block(params, img, txt, cond, cfg: DiTConfig, rope=None):
    """Dual-stream MMDiT block: separate params, joint attention over
    [txt | img] computed once, each stream's slice through its own
    ``wo``."""
    fused = _fuses_qk(rope, cfg.d_model // cfg.n_heads)
    streams = {"img": img, "txt": txt}
    hs, qkvs, mods = {}, {}, {}
    for name in ("img", "txt"):
        p = params[name]
        mods[name] = _modulation(p["mod"], cond, 6)
        sh1, sc1 = mods[name][0], mods[name][1]
        h = common.layernorm(streams[name], cfg.norm_eps) * (1 + sc1) + sh1
        if fused:
            hs[name] = h
        else:
            qkvs[name] = _qkv_heads(p["attn"], h, cfg.n_heads)
    s_txt = txt.shape[1]
    if fused:
        q, k, v = _joint_qkv([params["txt"]["attn"], params["img"]["attn"]],
                             [hs["txt"], hs["img"]], cfg.n_heads, rope)
    else:
        q, k, v = (jnp.concatenate([qkvs["txt"][i], qkvs["img"][i]], axis=1)
                   for i in range(3))
        q, k = apply_rope(q, rope), apply_rope(k, rope)
    o = _attention(q, k, v)
    parts = {"txt": o[:, :s_txt], "img": o[:, s_txt:]}
    outs = {}
    for name in ("img", "txt"):
        p = params[name]
        _, _, g1, sh2, sc2, g2 = mods[name]
        x = streams[name] + g1 * _project_out(parts[name], p["attn"]["wo"],
                                              img.dtype)
        h = common.layernorm(x, cfg.norm_eps) * (1 + sc2) + sh2
        y = jax.nn.gelu(h @ p["mlp"]["wi"].astype(x.dtype))
        outs[name] = x + g2 * (y @ p["mlp"]["wo"].astype(x.dtype))
    return outs["img"], outs["txt"]


def dit_specs(cfg: DiTConfig):
    pdim = cfg.patch_size * cfg.patch_size * cfg.in_channels
    s: Dict[str, Any] = {
        "patch_proj": common.dense_specs(pdim, cfg.d_model, None, "embed",
                                         use_bias=True),
        "time_mlp1": common.dense_specs(cfg.time_embed_dim, cfg.d_model,
                                        None, "embed", use_bias=True),
        "time_mlp2": common.dense_specs(cfg.d_model, cfg.d_model,
                                        "embed", None, use_bias=True),
        "single": common.stack_specs(single_block_specs(cfg), cfg.n_layers),
        "final_mod": _mod_specs(cfg.d_model, 2),
        "final_proj": ParamSpec((cfg.d_model, pdim), ("embed", None),
                                init="zeros"),
    }
    if cfg.n_double > 0:
        s["double"] = common.stack_specs(double_block_specs(cfg), cfg.n_double)
    if cfg.text_dim > 0:
        s["text_proj"] = common.dense_specs(cfg.text_dim, cfg.d_model, None,
                                            "embed", use_bias=True)
    # FLUX's MLPEmbedders beside time_in (time_mlp1/2): guidance_in over
    # the guidance scale's sinusoidal features, vector_in over the
    # pooled text vector
    if cfg.guidance_embed:
        s.update(_embedder_specs("guidance", cfg.time_embed_dim, cfg.d_model))
    if cfg.vec_in_dim > 0:
        s.update(_embedder_specs("vector", cfg.vec_in_dim, cfg.d_model))
    return s


def _embedder_specs(name: str, d_in: int, d: int):
    return {f"{name}_mlp1": common.dense_specs(d_in, d, None, "embed",
                                               use_bias=True),
            f"{name}_mlp2": common.dense_specs(d, d, "embed", None,
                                               use_bias=True)}


def _embed(params, name: str, x):
    """Linear, SiLU, Linear (FLUX's ``MLPEmbedder``)."""
    h = jax.nn.silu(common.dense(params[f"{name}_mlp1"], x))
    return common.dense(params[f"{name}_mlp2"], h)


def _time_cond(params, t, cfg: DiTConfig, dtype, guidance=None,
               pooled=None):
    """``vec`` [B, d]: the time embedding, plus for a FLUX configuration
    the guidance and pooled-text embeddings."""
    emb = timestep_embedding(t, cfg.time_embed_dim).astype(dtype)
    vec = _embed(params, "time", emb)
    if cfg.guidance_embed:
        if guidance is None:
            raise ValueError(f"{cfg.arch_id} embeds guidance: pass the "
                             "guidance scale")
        vec = vec + _embed(params, "guidance", timestep_embedding(
            guidance, cfg.time_embed_dim).astype(dtype))
    if cfg.vec_in_dim > 0:
        if pooled is None:
            raise ValueError(f"{cfg.arch_id} embeds a pooled text vector "
                             f"of {cfg.vec_in_dim}: pass it")
        vec = vec + _embed(params, "vector", pooled.astype(dtype))
    return vec


def dit_forward(params, latents: jnp.ndarray, t: jnp.ndarray,
                cfg: DiTConfig,
                text_embeds: Optional[jnp.ndarray] = None, *,
                pooled: Optional[jnp.ndarray] = None,
                guidance: Optional[jnp.ndarray] = None,
                ref_latents: Optional[jnp.ndarray] = None
                ) -> DenoiserOutput:
    """latents: [B,H,W,C]; t: [B] in [0,1]; text_embeds: [B,T,text_dim];
    pooled: [B, vec_in_dim]; guidance: [B]; ref_latents: [B,H',W',C],
    patchified through ``patch_proj`` and joined after the image tokens.

    The dual-stream blocks take [text | image + reference], the
    single-stream blocks [text | image | reference]; the CRF and the
    velocity cover the image tokens only."""
    b, h, w, c = latents.shape
    p = cfg.patch_size
    dtype = jnp.dtype(cfg.dtype)
    x = patchify(latents.astype(dtype), p)
    x = common.dense(params["patch_proj"], x)
    s_img = x.shape[1]
    if not cfg.rope_axes:
        x = x + _pos_embedding(s_img, cfg.d_model).astype(dtype)[None]
    ref_grid = None
    if ref_latents is not None:
        ref_grid = (ref_latents.shape[1] // p, ref_latents.shape[2] // p)
        ref = common.dense(params["patch_proj"],
                           patchify(ref_latents.astype(dtype), p))
        x = jnp.concatenate([x, ref], axis=1)
    cond = _time_cond(params, t, cfg, dtype, guidance, pooled)

    txt = None
    if cfg.text_dim > 0 and text_embeds is not None:
        txt = common.dense(params["text_proj"], text_embeds.astype(dtype))
    s_txt = 0 if txt is None else txt.shape[1]
    rope = None
    if cfg.rope_axes:
        rope = rope_tables(token_ids(s_txt, (h // p, w // p), ref_grid),
                           cfg.rope_axes, cfg.rope_theta)

    if cfg.n_double > 0 and txt is not None:
        def dbody(carry, layer_params):
            img_h, txt_h = carry
            return double_block(layer_params, img_h, txt_h, cond, cfg,
                                rope), ()
        with jax.named_scope("dit.double_blocks"):
            (x, txt), _ = jax.lax.scan(dbody, (x, txt), params["double"])

    if txt is not None:
        x = jnp.concatenate([txt, x], axis=1)

    def sbody(h_tok, layer_params):
        return single_block(layer_params, h_tok, cond, cfg, rope), ()

    with jax.named_scope("dit.single_blocks"):
        x, _ = jax.lax.scan(sbody, x, params["single"])
    crf = x[:, s_txt:s_txt + s_img]
    velocity = _final_layer(params, crf, cond, cfg, h, w)
    return DenoiserOutput(velocity=velocity, crf=crf)


def _final_layer(params, crf, cond, cfg: DiTConfig, h: int, w: int):
    sh, sc = _modulation(params["final_mod"], cond, 2)
    y = common.layernorm(crf, cfg.norm_eps) * (1 + sc) + sh
    y = y @ params["final_proj"].astype(crf.dtype)
    return unpatchify(y, h, w, cfg.patch_size, cfg.in_channels)


def dit_from_crf(params, crf: jnp.ndarray, t: jnp.ndarray, cfg: DiTConfig,
                 h: int, w: int, *, pooled: Optional[jnp.ndarray] = None,
                 guidance: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """FreqCa skip path: predicted CRF -> velocity (final layer only,
    modulated by ``vec``)."""
    cond = _time_cond(params, t, cfg, crf.dtype, guidance, pooled)
    return _final_layer(params, crf, cond, cfg, h, w)


# a request's conditioning pytree: key -> ``dit_forward`` keyword
COND_KEYS = {"txt": "text_embeds", "vec": "pooled", "guidance": "guidance",
             "ref_latents": "ref_latents"}


def cond_kwargs(cond) -> Dict[str, Any]:
    """``dit_forward``'s keywords from a batch's conditioning pytree: a
    dict with any of ``txt`` [B, T, text_dim], ``vec`` [B, vec_in_dim],
    ``guidance`` [B] and ``ref_latents`` [B, H, W, C]; an empty pytree
    (``()``) conditions on time alone."""
    cond = dict(cond or {})
    unknown = set(cond) - set(COND_KEYS)
    if unknown:
        raise ValueError(f"unknown conditioning {sorted(unknown)}; "
                         f"expected keys of {sorted(COND_KEYS)}")
    return {COND_KEYS[k]: v for k, v in cond.items()}


def denoiser(cfg: DiTConfig):
    """The sampler's denoiser pair for ``cfg``: ``full_fn(params, x, t,
    cond) -> (velocity, crf)`` and ``from_crf_fn(params, crf, t, cond) ->
    velocity``, ``cond`` the batch's conditioning pytree (``cond_kwargs``;
    ``()`` for none).  The cached step reads ``vec``'s guidance and
    pooled text from it too.

    Weights arrive as the ``params`` argument (never closed over), so a
    jitted sampler takes them as inputs.  ``from_crf_fn`` is
    shape-generic: the square image side is recovered from the CRF's
    token count, so one pair serves a whole shape ladder."""
    def full_fn(params, x, t, cond=()):
        tb = jnp.full((x.shape[0],), t)
        out = dit_forward(params, x, tb, cfg, **cond_kwargs(cond))
        return out.velocity, out.crf

    def from_crf_fn(params, crf, t, cond=()):
        tb = jnp.full((crf.shape[0],), t)
        side = math.isqrt(crf.shape[1]) * cfg.patch_size
        kw = cond_kwargs(cond)
        return dit_from_crf(params, crf, tb, cfg, side, side,
                            pooled=kw.get("pooled"),
                            guidance=kw.get("guidance"))

    return full_fn, from_crf_fn


def random_params(cfg: DiTConfig, seed: int, device=None):
    """Seeded random weights in ``cfg.dtype``, every leaf drawn.

    The AdaLN-zero modulations and the zero-initialised output
    projection of ``dit_specs`` make an untrained model's blocks the
    identity and its velocity zero; drawing them too gives a model whose
    forward does real work (checks and smoke runs without trained
    weights).  One jitted program, so no f32 copy of the weights is
    ever held; with ``device`` it runs there and the weights are made
    in place."""
    specs = jax.tree.map(
        lambda s: dataclasses.replace(s, init="normal")
        if s.init == "zeros" else s,
        dit_specs(cfg), is_leaf=lambda x: isinstance(x, ParamSpec))
    out = (None if device is None
           else jax.sharding.SingleDeviceSharding(device))
    init = jax.jit(lambda k: common.init_params(specs, k,
                                                jnp.dtype(cfg.dtype)),
                   out_shardings=out)
    return init(jax.random.key(seed))


# ---------------------------------------------------------------------------
# assigned-architecture backbones as denoisers
# ---------------------------------------------------------------------------

def backbone_denoiser_specs(cfg: ModelConfig, patch_size: int = 2,
                            in_channels: int = 4, time_dim: int = 256):
    pdim = patch_size * patch_size * in_channels
    return {
        "patch_proj": common.dense_specs(pdim, cfg.d_model, None, "embed",
                                         use_bias=True),
        "time_mlp1": common.dense_specs(time_dim, cfg.d_model, None, "embed",
                                        use_bias=True),
        "time_mlp2": common.dense_specs(cfg.d_model, cfg.d_model, "embed",
                                        None, use_bias=True),
        "stack": blocks.stack_specs(cfg),
        "final_norm": common.rmsnorm_specs(cfg.d_model),
        "final_proj": ParamSpec((cfg.d_model, pdim), ("embed", None),
                                init="zeros"),
    }


def backbone_denoiser_forward(params, latents, t, cfg: ModelConfig,
                              patch_size: int = 2, time_dim: int = 256
                              ) -> DenoiserOutput:
    b, hh, ww, c = latents.shape
    dtype = jnp.dtype(cfg.dtype)
    x = patchify(latents.astype(dtype), patch_size)
    x = common.dense(params["patch_proj"], x)
    x = x + _pos_embedding(x.shape[1], cfg.d_model).astype(dtype)[None]
    emb = timestep_embedding(t, time_dim).astype(dtype)
    temb = common.dense(params["time_mlp2"],
                        jax.nn.silu(common.dense(params["time_mlp1"], emb)))
    x = x + temb[:, None, :]
    h, _ = blocks.stack_full(params["stack"], x, cfg, causal=False)
    y = common.rmsnorm(params["final_norm"], h, cfg.norm_eps)
    y = y @ params["final_proj"].astype(y.dtype)
    velocity = unpatchify(y, hh, ww, patch_size, c)
    return DenoiserOutput(velocity=velocity, crf=h)


def backbone_denoiser_from_crf(params, crf, cfg: ModelConfig, h: int, w: int,
                               patch_size: int = 2, in_channels: int = 4):
    y = common.rmsnorm(params["final_norm"], crf, cfg.norm_eps)
    y = y @ params["final_proj"].astype(y.dtype)
    return unpatchify(y, h, w, patch_size, in_channels)
