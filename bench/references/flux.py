"""Plain float32 reference of a FLUX.1-Kontext configuration served with
a feature cache: weights from the seed, the denoiser forward with its
conditioning, the cache policy and the rectified-flow Euler sampler,
one image at a time.

It imports nothing of the program under test; the weight layout, the
arithmetic helpers and the FreqCa schedule, band split and Hermite
prediction are those of ``bench/references/dit.py``.  It follows the
equations of FLUX.1-Kontext-dev (github.com/black-forest-labs/flux,
``src/flux/model.py``, ``sampling.py``):

* weights: every leaf of the parameter tree drawn from one key split in
  the order of the sorted tree, as the ``dit`` reference draws them,
  with FLUX's embedders beside the time MLP: ``guidance_mlp1/2``
  (``guidance_in``) and ``vector_mlp1/2`` (``vector_in``).
* ``vec`` = time_in(t) + guidance_in(g) + vector_in(pooled), each a
  Linear, SiLU, Linear over the sinusoidal features of 1000·t (1000·g)
  or the pooled vector.
* tokens: the image and the reference latents patchified through the
  same ``img_in`` (``patch_proj``), the text through ``txt_in``
  (``text_proj``); ids text (0, 0, 0), image (0, h, w), reference
  (1, h, w); RoPE per axis (16, 56, 56) at theta 10000, rotating
  adjacent channel pairs of q and k after the q/k norm.
* dual-stream blocks: per stream a 6-way modulation of ``vec``,
  LayerNorm, Q/K/V, a LayerNorm over each head (scaled); attention over
  [text | image + reference]; each stream's slice through its own
  output projection, gated residual, LayerNorm, tanh-GELU MLP.
* single-stream blocks over [text | image | reference], the same block
  with one set of weights.
* the cached feature is the single blocks' output on the image tokens;
  the velocity the 2-way modulated final layer over it.

Departures from FLUX, the served model's own: single-stream blocks run
attention then the MLP in sequence (FLUX runs both from one fused
projection, in parallel; the operations per token are the same); the
q/k norm is a LayerNorm with scale (FLUX: RMSNorm with scale); Q/K/V
and the final projection carry no bias.

Attention runs in query blocks of at most ``Q_BLOCK`` rows, so one image
at 8704 tokens fits beside the weights.  Every matrix product runs at
``Precision.HIGHEST`` on float32 copies of the weights;
``quant="fp8"`` rounds both operands of every product to float8_e4m3
(the control).
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench import cell as cell_lib
from bench import loadgen

_dit = cell_lib.reference("dit")
F32, HIGHEST = _dit.F32, _dit.HIGHEST
_ein, _layernorm, _gelu, _silu = (_dit._ein, _dit._layernorm, _dit._gelu,
                                  _dit._silu)
_modulation, _patchify, _unpatchify = (_dit._modulation, _dit._patchify,
                                       _dit._unpatchify)
rel_err, schedule = _dit.rel_err, _dit.schedule
Q_BLOCK = 1024


# --- weights --------------------------------------------------------------

def leaf_table(model: dict) -> dict:
    d = model["d_model"]
    tree = _dit.leaf_table(model)
    tree.update({"guidance_mlp1": _dit._dense(model["time_embed_dim"], d),
                 "guidance_mlp2": _dit._dense(d, d),
                 "vector_mlp1": _dit._dense(model["vec_in_dim"], d),
                 "vector_mlp2": _dit._dense(d, d)})
    return tree


def make_weights(model: dict, seed: int) -> dict:
    """Every leaf, made on the device in one call, in the configuration's
    dtype."""
    leaves = _dit._flat(leaf_table(model))
    dtype = jnp.dtype(model["dtype"])

    def gen(key):
        keys = jax.random.split(key, len(leaves))
        out: dict = {}
        for i, (path, (shape, init)) in enumerate(leaves):
            if init == "ones":
                v = jnp.ones(shape, dtype)
            else:
                v = (jax.random.normal(keys[i], shape)
                     * _dit._std(shape)).astype(dtype)
            node = out
            for k in path[:-1]:
                node = node.setdefault(k, {})
            node[path[-1]] = v
        return out

    return jax.jit(gen)(jax.random.key(seed))


# --- forward ----------------------------------------------------------------

def _dense(p, x, q):
    return _ein("sk,kd->sd", x, p["kernel"], q) + p["bias"].astype(F32)


def _sinusoid(v, dim):
    half = dim // 2
    freqs = jnp.exp(-math.log(10000.0) * jnp.arange(half, dtype=F32) / half)
    arg = v * 1000.0 * freqs
    return jnp.concatenate([jnp.cos(arg), jnp.sin(arg)])[None]


def _mlp_embed(w, name, x, q):
    return _dense(w[f"{name}_mlp2"], _silu(_dense(w[f"{name}_mlp1"], x, q)),
                  q)


def _vec(w, t, cond, model, q):
    e = model["time_embed_dim"]
    return (_mlp_embed(w, "time", _sinusoid(t, e), q)
            + _mlp_embed(w, "guidance", _sinusoid(cond["guidance"], e), q)
            + _mlp_embed(w, "vector", cond["vec"][None], q))     # [1, d]


def rope_angles(s_txt: int, grid: tuple, ref_grid: tuple, axes,
                theta: float) -> np.ndarray:
    """[S, sum(axes) / 2] RoPE angles of [text | image | reference], in
    float64 on the host (FLUX's ``rope`` computes them in float64)."""
    def ids(index, hp, wp):
        r, c = np.meshgrid(np.arange(hp), np.arange(wp), indexing="ij")
        return np.stack([np.full(hp * wp, index), r.ravel(), c.ravel()], -1)
    pos = np.concatenate([np.zeros((s_txt, 3)), ids(0, *grid),
                          ids(1, *ref_grid)]).astype(np.float64)
    return np.concatenate(
        [pos[:, i:i + 1] / theta ** (np.arange(0, dim, 2) / dim)
         for i, dim in enumerate(axes)], -1)


def _rope(x, cos, sin):
    """x [S, H, hd]: each pair (2j, 2j+1) rotated by angle j."""
    s, h, hd = x.shape
    x0, x1 = x[..., 0::2], x[..., 1::2]
    c, sn = cos[:, None], sin[:, None]
    return jnp.stack([x0 * c - x1 * sn, x0 * sn + x1 * c],
                     -1).reshape(s, h, hd)


def _attention(qh, kh, vh, q):
    """Softmax attention over every token, computed in query blocks."""
    s, h, hd = qh.shape
    qb = min(s, Q_BLOCK)
    nb = -(-s // qb)
    blocks = jnp.pad(qh, ((0, nb * qb - s), (0, 0), (0, 0))).reshape(
        nb, qb, h, hd)

    def one(qi):
        logits = _ein("shk,thk->hst", qi, kh, q) / math.sqrt(hd)
        return _ein("hst,thk->shk", jax.nn.softmax(logits, axis=-1), vh, q)

    return jax.lax.map(one, blocks).reshape(nb * qb, h, hd)[:s]


def _qkv(a, y, q, rope):
    cos, sin = rope
    qh = _layernorm(_ein("sd,dhk->shk", y, a["wq"], q)) \
        * a["q_norm"].astype(F32)
    kh = _layernorm(_ein("sd,dhk->shk", y, a["wk"], q)) \
        * a["k_norm"].astype(F32)
    return _rope(qh, cos, sin), _rope(kh, cos, sin), \
        _ein("sd,dhk->shk", y, a["wv"], q)


def _mlp(m, y, q):
    return _ein("sf,fd->sd", _gelu(_ein("sd,df->sf", y, m["wi"], q)),
                m["wo"], q)


def _double(img, txt, p, vec, rope, model, q):
    eps, n_txt = model["norm_eps"], txt.shape[0]
    streams = {"txt": txt, "img": img}
    mods, qkv = {}, {}
    for k in ("txt", "img"):
        mods[k] = _modulation(p[k]["mod"], vec, 6, q)
        sh1, sc1 = mods[k][:2]
        y = _layernorm(streams[k], eps) * (1 + sc1) + sh1
        qkv[k] = _qkv(p[k]["attn"], y, q, (rope[0][:n_txt], rope[1][:n_txt])
                      if k == "txt" else (rope[0][n_txt:], rope[1][n_txt:]))
    o = _attention(*(jnp.concatenate([qkv["txt"][i], qkv["img"][i]])
                     for i in range(3)), q)
    out = {}
    for k, part in (("txt", o[:n_txt]), ("img", o[n_txt:])):
        _, _, g1, sh2, sc2, g2 = mods[k]
        x = streams[k] + g1 * _ein("shk,hkd->sd", part, p[k]["attn"]["wo"],
                                   q)
        y = _layernorm(x, eps) * (1 + sc2) + sh2
        out[k] = x + g2 * _mlp(p[k]["mlp"], y, q)
    return out["img"], out["txt"]


def _single(x, p, vec, rope, model, q):
    eps = model["norm_eps"]
    sh1, sc1, g1, sh2, sc2, g2 = _modulation(p["mod"], vec, 6, q)
    y = _layernorm(x, eps) * (1 + sc1) + sh1
    o = _attention(*_qkv(p["attn"], y, q, rope), q)
    x = x + g1 * _ein("shk,hkd->sd", o, p["attn"]["wo"], q)
    y = _layernorm(x, eps) * (1 + sc2) + sh2
    return x + g2 * _mlp(p["mlp"], y, q)


def _final(w, feat, vec, model, lat_shape, q):
    sh, sc = _modulation(w["final_mod"], vec, 2, q)
    y = _layernorm(feat, model["norm_eps"]) * (1 + sc) + sh
    y = _ein("sd,dp->sp", y, w["final_proj"], q)
    return _unpatchify(y, *lat_shape[:2], model["patch_size"], lat_shape[2])


def _forward(w, lat, t, cond, model, rope, q):
    """-> (velocity [H, W, C], feature [S, d] of the image tokens)."""
    p = model["patch_size"]
    img = _dense(w["patch_proj"], _patchify(lat, p), q)
    ref = _dense(w["patch_proj"], _patchify(cond["ref_latents"], p), q)
    txt = _dense(w["text_proj"], cond["txt"], q)
    vec = _vec(w, t, cond, model, q)
    s_img, n_txt = img.shape[0], txt.shape[0]

    def dbody(carry, blk):
        return _double(*carry, blk, vec, rope, model, q), None

    (x, txt), _ = jax.lax.scan(dbody, (jnp.concatenate([img, ref]), txt),
                               w["double"])

    def sbody(h, blk):
        return _single(h, blk, vec, rope, model, q), None

    x, _ = jax.lax.scan(sbody, jnp.concatenate([txt, x]), w["single"])
    feat = x[n_txt:n_txt + s_img]
    return _final(w, feat, vec, model, lat.shape, q), feat


# --- sampler -------------------------------------------------------------

class Reference:
    """The reference for one configuration and policy, compiled once and
    run one image at a time."""

    def __init__(self, model: dict, policy: dict, n_steps: int,
                 lat_shape: tuple, quant: Optional[str] = None):
        self.model, self.policy, self.n_steps = model, policy, n_steps
        self.lat_shape = tuple(lat_shape)
        q = _dit._operands(quant)
        m = model
        grid = (lat_shape[0] // m["patch_size"],
                lat_shape[1] // m["patch_size"])
        ang = rope_angles(m["n_text_tokens"], grid, grid, m["rope_axes"],
                          m["rope_theta"])
        rope = (jnp.asarray(np.cos(ang), F32), jnp.asarray(np.sin(ang), F32))
        self._full = jax.jit(lambda w, x, t, c: _forward(w, x, t, c, m, rope,
                                                         q))
        self._cached = jax.jit(lambda w, f, t, c: _final(
            w, f, _vec(w, t, c, m, q), m, self.lat_shape, q))
        s = grid[0] * grid[1]
        if policy["name"] == "freqca":
            if policy.get("method", "dct") != "dct":
                raise ValueError("the reference splits bands by DCT only")
            basis = jnp.asarray(_dit.dct_low_basis(s, policy["rho"]), F32)
            self._split = jax.jit(lambda f: _dit.Reference._split_fn(
                f, basis, q))
            self._rebuild = jax.jit(lambda lo, hs, wts: _ein(
                "ms,md->sd", basis, lo, q) + jnp.einsum(
                    "k,ksd->sd", wts, hs, precision=HIGHEST))
        self.schedule = schedule(policy, n_steps)

    def x_init(self, seed: int):
        return jax.random.normal(jax.random.key(seed), self.lat_shape, F32)

    def sample(self, weights, x, cond):
        """-> (final latents, number of full steps)."""
        cond = {k: jnp.asarray(v, F32) for k, v in cond.items()}
        ts = np.linspace(1.0, 0.0, self.n_steps + 1).astype(np.float32)
        order = self.policy.get("high_order", 2)
        low, highs = None, []                  # highs: [(t, feature)]
        for i, full in enumerate(self.schedule):
            t = jnp.float32(ts[i])
            if full:
                v, feat = self._full(weights, x, t, cond)
                if self.policy["name"] == "freqca":
                    low, high = self._split(feat)
                    highs = (highs + [(float(ts[i]), high)])[-(order + 1):]
            else:
                wts = _dit.extrapolation_weights([h[0] for h in highs],
                                                 float(ts[i]), order)
                feat = self._rebuild(low, jnp.stack([h[1] for h in highs]),
                                     jnp.asarray(wts, F32))
                v = self._cached(weights, feat, t, cond)
            x = x + (ts[i + 1] - ts[i]) * v
        return x, sum(self.schedule)


def conditioning(cell, a: loadgen.Arrival, lat: tuple) -> dict:
    """An arrival's conditioning, drawn from a fold of its seed as the
    program file's ``request`` draws it: text and pooled vector uniform
    of unit variance, guidance uniform over the traffic's range,
    reference latents a smooth field plus fine noise."""
    m = cell.model
    rng = np.random.default_rng(loadgen.fold(a.seed, "cond"))
    root12 = np.float32(np.sqrt(12.0))

    def unit(shape):
        return (rng.random(shape, dtype=np.float32) - np.float32(0.5)) \
            * root12

    txt = unit((m["n_text_tokens"], m["text_dim"]))
    vec = unit((m["vec_in_dim"],))
    lo, hi = cell.traffic["guidance"]
    guidance = np.float32(lo + (hi - lo) * rng.random())
    h, w, c = lat
    fy, fx, ph = (rng.uniform(0.5, 3.0, c).astype(np.float32)
                  for _ in range(3))
    ay = 2 * np.pi * (fy * np.linspace(0, 1, h, dtype=np.float32)[:, None]
                      + ph)
    ax = 2 * np.pi * fx * np.linspace(0, 1, w, dtype=np.float32)[:, None]
    field = (np.sin(ay)[:, None] * np.cos(ax)[None]
             + np.cos(ay)[:, None] * np.sin(ax)[None])
    ref = field + np.float32(0.1) * unit(lat)
    return {"txt": txt, "vec": vec, "guidance": np.asarray(guidance),
            "ref_latents": ref.astype(np.float32)}


def inputs(ref: Reference, cell, a: loadgen.Arrival) -> dict:
    """The keyword inputs of ``ref.sample`` for arrival ``a``: its start
    noise and its conditioning."""
    return {"x": ref.x_init(a.seed),
            "cond": conditioning(cell, a, ref.lat_shape)}
