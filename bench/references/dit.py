"""Plain float32 reference of a diffusion transformer served with a
feature cache: weights from the seed, the denoiser forward, the cache
policy and the rectified-flow Euler sampler, one image at a time.

It imports nothing of the program under test.  It follows the model the
configuration describes:

* weights: every leaf drawn from one key split in the order of the
  sorted parameter tree, fan-in scaled normals (the first axis of a
  stacked leaf of four axes, the second of three, else the first) in
  the configuration's dtype; the q/k norm scales are ones.  Only the
  blocks the served path runs are made here.
* forward: patch embedding plus 1-D sincos positions; a sinusoidal time
  embedding through a two-layer SiLU MLP; per single-stream block a
  6-way adaLN-zero modulation, LayerNorm, Q/K/V with a LayerNorm over
  each head (scaled), full softmax attention, output projection, then
  LayerNorm, tanh-GELU MLP; a 2-way modulated final layer.  The
  feature a cache keeps is the residual stream after the last block.
* policies: ``none`` runs every step in full.  ``freqca`` runs step i in
  full when ``i % interval == 0`` or fewer than ``max(low_order,
  high_order) + 1`` full steps have run; a full step splits the feature
  over tokens into its first ``m = round(S·rho)`` orthonormal DCT-II
  coefficients and the spatial remainder; a cached step rebuilds the
  low band from the newest coefficients (order 0) and extrapolates the
  remainder by the least-squares polynomial of degree ``high_order``
  through the newest ``high_order + 1`` full steps, then runs the final
  layer on the sum.
* sampling: times ``linspace(1, 0, n_steps + 1)``, ``x += (t' − t)·v``
  from seeded unit noise, or for an edit from ``(1 − s)·ref + s·noise``.
* inputs: ``inputs`` gives, from an arrival, every input of
  ``Reference.sample`` but the weights, drawn from the arrival's seed as
  the program file's ``request`` draws them.

Every matrix product runs at ``Precision.HIGHEST`` on float32 copies of
the weights.  ``quant="fp8"`` instead rounds both operands of every
product to float8_e4m3 with a per-tensor scale: the control, computed
one precision below the configuration's bfloat16.
"""
from __future__ import annotations

import math
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench import loadgen

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0


# --- weights --------------------------------------------------------------

def _block_leaves(model: dict, n: int) -> dict:
    d, f, h = model["d_model"], model["d_ff"], model["n_heads"]
    hd = d // h
    return {"attn": {"k_norm": ((n, hd), "ones"), "q_norm": ((n, hd), "ones"),
                     "wk": ((n, d, h, hd), "normal"),
                     "wo": ((n, h, hd, d), "normal"),
                     "wq": ((n, d, h, hd), "normal"),
                     "wv": ((n, d, h, hd), "normal")},
            "mlp": {"wi": ((n, d, f), "normal"), "wo": ((n, f, d), "normal")},
            "mod": {"bias": ((n, 6 * d), "normal"),
                    "kernel": ((n, d, 6 * d), "normal")}}


def _dense(d_in: int, d_out: int) -> dict:
    return {"bias": ((d_out,), "normal"), "kernel": ((d_in, d_out), "normal")}


def leaf_table(model: dict) -> dict:
    """The parameter tree as ``{name: (shape, init)}`` nests."""
    d = model["d_model"]
    pdim = model["patch_size"] ** 2 * model["in_channels"]
    tree = {"final_mod": {"bias": ((2 * d,), "normal"),
                          "kernel": ((d, 2 * d), "normal")},
            "final_proj": ((d, pdim), "normal"),
            "patch_proj": _dense(pdim, d),
            "single": _block_leaves(model, model["n_layers"]),
            "time_mlp1": _dense(model["time_embed_dim"], d),
            "time_mlp2": _dense(d, d)}
    if model.get("n_double", 0) > 0:
        blk = _block_leaves(model, model["n_double"])
        tree["double"] = {"img": blk, "txt": blk}
    if model.get("text_dim", 0) > 0:
        tree["text_proj"] = _dense(model["text_dim"], d)
    return tree


def _flat(tree, prefix=()):
    if isinstance(tree, tuple):
        return [(prefix, tree)]
    out = []
    for k in sorted(tree):
        out += _flat(tree[k], prefix + (k,))
    return out


def _std(shape) -> float:
    fan_in = shape[1] if len(shape) == 3 else shape[0]
    return 1.0 / math.sqrt(max(fan_in, 1))


def make_weights(model: dict, seed: int) -> dict:
    """The weights the served path uses (no dual-stream or text leaves),
    made on the device in one call, in the configuration's dtype."""
    leaves = _flat(leaf_table(model))
    dtype = jnp.dtype(model["dtype"])
    wanted = [(i, path, spec) for i, (path, spec) in enumerate(leaves)
              if path[0] not in ("double", "text_proj")]

    def gen(key):
        keys = jax.random.split(key, len(leaves))
        out: dict = {}
        for i, path, (shape, init) in wanted:
            if init == "ones":
                v = jnp.ones(shape, dtype)
            else:
                v = (jax.random.normal(keys[i], shape)
                     * _std(shape)).astype(dtype)
            node = out
            for k in path[:-1]:
                node = node.setdefault(k, {})
            node[path[-1]] = v
        return out

    return jax.jit(gen)(jax.random.key(seed))


# --- arithmetic -------------------------------------------------------------

def _fp8(a):
    scale = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / FP8_MAX
    return (a / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale


def _operands(quant: Optional[str]):
    if quant is None:
        return lambda a: a.astype(F32)
    if quant == "fp8":
        return lambda a: _fp8(a.astype(F32))
    raise ValueError(f"unknown quant {quant!r}")


def _ein(spec, a, b, q):
    return jnp.einsum(spec, q(a), q(b), precision=HIGHEST,
                      preferred_element_type=F32)


def _layernorm(x, eps=1e-6):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps)


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                     * (x + 0.044715 * x ** 3)))


def _silu(x):
    return x / (1.0 + jnp.exp(-x))


def _time_cond(w, t, model, q):
    half = model["time_embed_dim"] // 2
    freqs = jnp.exp(-math.log(10000.0) * jnp.arange(half, dtype=F32) / half)
    arg = t * 1000.0 * freqs
    emb = jnp.concatenate([jnp.cos(arg), jnp.sin(arg)])[None]
    h = _silu(_ein("ij,jk->ik", emb, w["time_mlp1"]["kernel"], q)
              + w["time_mlp1"]["bias"].astype(F32))
    return (_ein("ij,jk->ik", h, w["time_mlp2"]["kernel"], q)
            + w["time_mlp2"]["bias"].astype(F32))          # [1, d]


def _modulation(p, cond, n, q):
    m = _ein("ij,jk->ik", _silu(cond), p["kernel"], q) + p["bias"].astype(F32)
    return jnp.split(m, n, axis=-1)                          # n x [1, d]


def _positions(s, d):
    pos = jnp.arange(s, dtype=F32)[:, None]
    i = jnp.arange(d // 2, dtype=F32)[None]
    ang = pos / jnp.power(10000.0, 2 * i / d)
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], -1)


def _block(x, p, cond, model, q):
    s, d = x.shape
    h = model["n_heads"]
    hd = d // h
    sh1, sc1, g1, sh2, sc2, g2 = _modulation(p["mod"], cond, 6, q)
    a = p["attn"]
    y = _layernorm(x, model["norm_eps"]) * (1 + sc1) + sh1
    qh = _layernorm(_ein("sd,dhk->shk", y, a["wq"], q)) * a["q_norm"].astype(F32)
    kh = _layernorm(_ein("sd,dhk->shk", y, a["wk"], q)) * a["k_norm"].astype(F32)
    vh = _ein("sd,dhk->shk", y, a["wv"], q)
    logits = _ein("shk,thk->hst", qh, kh, q) / math.sqrt(hd)
    probs = jax.nn.softmax(logits, axis=-1)
    o = _ein("hst,thk->shk", probs, vh, q)
    x = x + g1 * _ein("shk,hkd->sd", o, a["wo"], q)
    y = _layernorm(x, model["norm_eps"]) * (1 + sc2) + sh2
    m = p["mlp"]
    return x + g2 * _ein("sf,fd->sd", _gelu(_ein("sd,df->sf", y, m["wi"], q)),
                         m["wo"], q)


def _patchify(lat, p):
    h, w, c = lat.shape
    x = lat.reshape(h // p, p, w // p, p, c).transpose(0, 2, 1, 3, 4)
    return x.reshape((h // p) * (w // p), p * p * c)


def _unpatchify(tok, h, w, p, c):
    x = tok.reshape(h // p, w // p, p, p, c).transpose(0, 2, 1, 3, 4)
    return x.reshape(h, w, c)


def _final(w, feat, cond, model, lat_shape, q):
    sh, sc = _modulation(w["final_mod"], cond, 2, q)
    y = _layernorm(feat, model["norm_eps"]) * (1 + sc) + sh
    y = _ein("sd,dp->sp", y, w["final_proj"], q)
    return _unpatchify(y, *lat_shape[:2], model["patch_size"], lat_shape[2])


def _forward(w, lat, t, model, q):
    """-> (velocity [H, W, C], feature [S, d])."""
    x = _patchify(lat, model["patch_size"])
    x = (_ein("sp,pd->sd", x, w["patch_proj"]["kernel"], q)
         + w["patch_proj"]["bias"].astype(F32))
    x = x + _positions(*x.shape)
    cond = _time_cond(w, t, model, q)

    def body(h, p):
        return _block(h, p, cond, model, q), None

    x, _ = jax.lax.scan(body, x, w["single"])
    return _final(w, x, cond, model, lat.shape, q), x


def _from_feature(w, feat, t, model, lat_shape, q):
    return _final(w, feat, _time_cond(w, t, model, q), model, lat_shape, q)


# --- cache policy --------------------------------------------------------

def dct_low_basis(s: int, rho: float) -> np.ndarray:
    """The first ``round(s·rho)`` rows of the orthonormal DCT-II."""
    m = min(max(int(round(s * rho)), 1), s)
    k = np.arange(m)[:, None]
    i = np.arange(s)[None, :]
    b = np.cos(np.pi * (2 * i + 1) * k / (2 * s)) * math.sqrt(2.0 / s)
    b[0] /= math.sqrt(2.0)
    return b


def extrapolation_weights(ts, t, order: int) -> np.ndarray:
    """Weights w with ``Σ w_j f(ts_j)`` the least-squares polynomial of
    degree ``order`` through (ts, f) evaluated at ``t``."""
    ts = np.asarray(ts, np.float64)
    v = np.vander(ts, order + 1, increasing=True)
    vq = np.vander(np.array([t], np.float64), order + 1, increasing=True)[0]
    return v @ np.linalg.solve(v.T @ v, vq)


def schedule(policy: dict, n_steps: int) -> List[bool]:
    if policy["name"] == "none":
        return [True] * n_steps
    if policy["name"] != "freqca":
        raise ValueError(f"no reference for policy {policy['name']!r}")
    need = max(policy.get("low_order", 0), policy.get("high_order", 2)) + 1
    full, n_valid = [], 0
    for i in range(n_steps):
        f = i % policy["interval"] == 0 or n_valid < need
        n_valid += f
        full.append(f)
    return full


# --- sampler -------------------------------------------------------------

class Reference:
    """The reference for one configuration and policy, compiled once and
    run one image at a time."""

    def __init__(self, model: dict, policy: dict, n_steps: int,
                 lat_shape: tuple, quant: Optional[str] = None):
        self.model, self.policy, self.n_steps = model, policy, n_steps
        self.lat_shape = tuple(lat_shape)
        q = _operands(quant)
        m = model
        self._full = jax.jit(lambda w, x, t: _forward(w, x, t, m, q))
        self._cached = jax.jit(lambda w, f, t: _from_feature(
            w, f, t, m, self.lat_shape, q))
        s = (lat_shape[0] // m["patch_size"]) * (lat_shape[1]
                                                 // m["patch_size"])
        if policy["name"] == "freqca":
            if policy.get("method", "dct") != "dct":
                raise ValueError("the reference splits bands by DCT only")
            basis = jnp.asarray(dct_low_basis(s, policy["rho"]), F32)
            self._split = jax.jit(lambda f: self._split_fn(f, basis, q))
            self._rebuild = jax.jit(lambda lo, hs, wts: _ein(
                "ms,md->sd", basis, lo, q) + jnp.einsum(
                    "k,ksd->sd", wts, hs, precision=HIGHEST))
        self.schedule = schedule(policy, n_steps)

    @staticmethod
    def _split_fn(feat, basis, q):
        low = _ein("ms,sd->md", basis, feat, q)
        return low, feat - _ein("ms,md->sd", basis, low, q)

    def x_init(self, seed: int, ref=None, strength: float = 0.0):
        noise = jax.random.normal(jax.random.key(seed), self.lat_shape, F32)
        if ref is None:
            return noise
        return (1.0 - strength) * jnp.asarray(ref, F32) + strength * noise

    def sample(self, weights, x):
        """-> (final latents, number of full steps)."""
        ts = np.linspace(1.0, 0.0, self.n_steps + 1).astype(np.float32)
        order = self.policy.get("high_order", 2)
        low, highs = None, []                  # highs: [(t, feature)]
        for i, full in enumerate(self.schedule):
            t = jnp.float32(ts[i])
            if full:
                v, feat = self._full(weights, x, t)
                if self.policy["name"] == "freqca":
                    low, high = self._split(feat)
                    highs = (highs + [(float(ts[i]), high)])[-(order + 1):]
            else:
                wts = extrapolation_weights([h[0] for h in highs],
                                            float(ts[i]), order)
                feat = self._rebuild(low, jnp.stack([h[1] for h in highs]),
                                     jnp.asarray(wts, F32))
                v = self._cached(weights, feat, t)
            x = x + (ts[i + 1] - ts[i]) * v
        return x, sum(self.schedule)


def inputs(ref: Reference, cell, a: loadgen.Arrival) -> dict:
    """The keyword inputs of ``ref.sample`` for arrival ``a``: its start
    latents, from the edit reference where ``a`` is an edit."""
    edit = loadgen.edit_reference(a, ref.lat_shape) if a.edit else None
    return {"x": ref.x_init(a.seed, edit,
                            cell.traffic.get("edit_strength", 0.0))}


def rel_err(got, want) -> float:
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / max(np.linalg.norm(want),
                                                  1e-30))
