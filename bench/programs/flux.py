"""The program's side of a ``flux`` configuration (FLUX.1-Kontext): its
denoiser pair, seeded weights on the device, the cache policy, the
served request of an arrival with its conditioning, the engine, and the
counts the metric readers take from the family.

Every request is an in-context edit generated from noise: it carries
``n_text_tokens`` T5 tokens of ``text_dim``, a pooled CLIP vector of
``vec_in_dim``, a guidance scale and the reference image's latents, all
drawn on the host from folds of the arrival's seed (``conditioning``).
The dual-stream blocks run over [text | image + reference], the
single-stream blocks over [text | image | reference]; the cached
feature and the velocity cover the generated image tokens only."""
from __future__ import annotations

import dataclasses

import numpy as np

from bench import loadgen, work


def _require_conditioned_serving() -> None:
    """Refuse a tree whose served path cannot take conditioning: its
    requests would be served without it, as another model."""
    from repro.configs.base import DiTConfig
    from repro.serving.scheduler import DiffusionRequest
    need = {DiffusionRequest: {"cond"},
            DiTConfig: {"vec_in_dim", "guidance_embed", "rope_axes",
                        "rope_theta"}}
    for cls, names in need.items():
        missing = names - {f.name for f in dataclasses.fields(cls)}
        if missing:
            raise SystemExit(f"the flux family needs {cls.__name__} fields "
                             f"{sorted(missing)}: this tree cannot serve "
                             "conditioned requests")


def config(model: dict, name: str):
    from repro.configs.base import DiTConfig
    _require_conditioned_serving()
    fields = {f.name for f in dataclasses.fields(DiTConfig)}
    return DiTConfig(arch_id=name, **{
        k: tuple(v) if isinstance(v, list) else v
        for k, v in model.items() if k in fields})


def denoiser(model: dict, name: str):
    from repro.models import dit
    return dit.denoiser(config(model, name))


def weights(model: dict, name: str, seed: int, device):
    from repro.models import dit
    return dit.random_params(config(model, name), seed, device)


def policy(spec: dict):
    """The registered policy class whose ``name`` is ``spec["name"]``,
    built from the rest of the spec."""
    from repro.core import policies
    from repro.core.policies import base

    policies.available()            # registers the built-in policies
    todo, found = [base.Policy], {}
    while todo:
        cls = todo.pop()
        found.setdefault(cls.name, cls)
        todo.extend(cls.__subclasses__())
    args = {k: v for k, v in spec.items() if k != "name"}
    return found[spec["name"]](**args)


def conditioning(cell, a: loadgen.Arrival, lat: tuple) -> dict:
    """One request's conditioning, from a fold of its seed: text tokens
    and the pooled vector of unit variance (uniform, the cheapest
    draw: a backlog's requests must reach the queue within the
    engine's ``max_wait_s`` of each other), a guidance scale uniform
    over the traffic's range, and reference latents of unit scale (a
    smooth seeded field plus fine noise)."""
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


def request(cell, a: loadgen.Arrival, lat: tuple):
    """The served request of arrival ``a``: generated from its noise seed,
    conditioned on its text, pooled vector, guidance and reference."""
    from repro.serving.scheduler import DiffusionRequest
    return DiffusionRequest(request_id=a.index, seed=a.seed,
                            cond=conditioning(cell, a, lat))


def engine(cell, full_fn, from_crf_fn, params, lat: tuple, crf: tuple,
           pol):
    """The engine the window drives, with the configuration's engine
    settings and the cache policy ``pol``."""
    from repro.serving.engine import DiffusionEngine
    eng = cell.engine
    return DiffusionEngine(full_fn, from_crf_fn, params, lat, crf, pol,
                           n_steps=eng["n_steps"],
                           max_batch=eng["max_batch"],
                           max_wait_s=eng["max_wait_s"])


def attention_tokens(model: dict, s: int) -> int:
    """Tokens each attention call runs over: the text, the ``s``
    generated image tokens and the ``s`` reference tokens."""
    return model["n_text_tokens"] + 2 * s


def flash_calls(model: dict) -> int:
    """Flash calls of one full lane-step: one per block of either kind
    (a dual-stream block attends once over both streams)."""
    return model["n_double"] + model["n_layers"]


def forward_flops(model: dict, s: int) -> float:
    """Operations of one forward of one image over ``s`` generated image
    tokens: the patch embedding of the image and the reference, the
    text projection, the time, guidance and pooled-vector embedders;
    per dual-stream block each stream's 6-way modulation and its Q/K/V/O
    and MLP over its tokens, and attention over all; per single-stream
    block the same over all tokens; the final layer over the image."""
    d, f, heads = model["d_model"], model["d_ff"], model["n_heads"]
    n_txt, pdim = model["n_text_tokens"], (model["patch_size"] ** 2
                                           * model["in_channels"])
    tokens = attention_tokens(model, s)
    attn = work.flash(1, tokens, heads, d // heads, model["dtype"]).flops
    per_token = 2.0 * 4 * d * d + 2.0 * 2 * d * f     # Q, K, V, O; MLP
    mod = 2.0 * d * 6 * d
    double = 2 * mod + per_token * tokens + attn
    single = mod + per_token * tokens + attn
    embed = (2.0 * (2 * s) * pdim * d                  # image + reference
             + 2.0 * n_txt * model["text_dim"] * d
             + 2.0 * (2 * model["time_embed_dim"] * d + 2 * d * d)
             + 2.0 * (model["vec_in_dim"] * d + d * d))
    final = 2.0 * d * 2 * d + 2.0 * s * d * pdim
    return (embed + model["n_double"] * double + model["n_layers"] * single
            + final)
